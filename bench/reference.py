"""The plain float32 references of the benchmark's configurations, the seeded
weights both they and the served program start from, and the library the
model modules share.

A configuration names its model module, ``bench/models/<name>.py``
(:func:`model`), which holds what is particular to that kind of model:
``init_weights(cfg, key)``, ``forward(cfg, params, x, act)``,
``quantized_weights(cfg, params)``, ``quantized_forward(cfg, qw, x,
steps)``, ``useful_ops_per_image(cfg)`` and ``to_ir(cfg, params)``.  The
entry points here (:func:`init_weights`, :func:`forward`,
:func:`quantized_weights`, :func:`quantized_forward`, :func:`act_ranges`)
hand each configuration to its model module.

Nothing here, and nothing on a model module's reference side, imports the
program: the weights are made by the benchmark (one jitted call from the
seed, on the device) and handed to the program's reader (a model module's
``to_ir``, the one function that imports the program) and to
:func:`forward` alike.  Parameter names follow the program's readers
(``conv0/w``, ``stem/w``, ``dw0/w``, ...), which is the only thing the two
share.

Batch-norm is the inference form with the stored statistics, which the
seeded weights set to identity (scale 1, bias 0, mean 0, var 1), folded into
its conv; every bias is zero, as a freshly initialised network has them.

:func:`quantized_forward` is the same model at the configuration's stated
precision (``act_bits``/``weight_bits``), computed in integer codes: what
the served program should compute, code for code, not merely approximate.
"""
from __future__ import annotations

import math
from types import ModuleType
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

import hooks


def model(cfg: dict) -> ModuleType:
    """The module of ``cfg``'s model family, ``bench/models/<family>.py``."""
    mod = hooks.module("models", cfg["family"])
    if mod is None:
        raise KeyError(f"no model module bench/models/{cfg['family']}.py "
                       f"for configuration {cfg['name']!r}")
    return mod


def init_weights(cfg: dict, key) -> Dict[str, jax.Array]:
    """Seeded He-style weights for ``cfg`` (fan-in scaled normals)."""
    return model(cfg).init_weights(cfg, key)


def forward(cfg: dict, params: Dict[str, jax.Array], x) -> jax.Array:
    """Float32 logits (B, n_classes) of images ``x`` (B, H, W, C).  Call it
    under ``jax.default_matmul_precision("highest")``: on a TPU a float32
    matmul or conv otherwise runs in bfloat16 passes."""
    return model(cfg).forward(cfg, params, x, _same)


def quantized_weights(cfg: dict, params) -> Dict[str, tuple]:
    """Per layer, ``(codes, scale, bias)`` at ``weight_bits``
    (:func:`weight_codes`)."""
    return model(cfg).quantized_weights(cfg, params)


def quantized_forward(cfg: dict, qw: Dict[str, tuple], x, steps):
    """Logits of the model at the configuration's precision, in the integer
    code domain (:class:`Codes`): the input and every layer output are
    ``act_bits`` codes on the power-of-two grids ``steps``
    (:func:`grid_steps`).  ``qw`` is :func:`quantized_weights`.  Call it
    under HIGHEST matmul precision: then every float32 sum of codes is
    exact."""
    return model(cfg).quantized_forward(cfg, qw, x, steps)


# -- the float32 model's pieces ------------------------------------------------

def _bn(params, layer: str, c: int) -> None:
    params[f"{layer}/scale"] = jnp.ones((c,), jnp.float32)
    params[f"{layer}/bias"] = jnp.zeros((c,), jnp.float32)
    params[f"{layer}/mean"] = jnp.zeros((c,), jnp.float32)
    params[f"{layer}/var"] = jnp.ones((c,), jnp.float32)


def _normal(key, shape, fan: int):
    return jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(float(fan))


def _conv(x, w, b, stride: int = 1, groups: int = 1):
    y = jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups)
    return y + b


def _maxpool(x, p: int):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, p, p, 1),
                                 (1, p, p, 1), "VALID")


def _folded(params, layer: str, bn: str, eps: float = 1e-5):
    """``layer``'s weight and bias with the inference batch-norm ``bn``
    folded in (per output channel, the weight's last axis)."""
    inv = params[f"{bn}/scale"] * jax.lax.rsqrt(params[f"{bn}/var"] + eps)
    b = (params[f"{layer}/b"] - params[f"{bn}/mean"]) * inv \
        + params[f"{bn}/bias"]
    return params[f"{layer}/w"] * inv, b


def _same(name, v):
    return v


# -- the configuration's precision: D<act_bits> activations, W<bits> weights --

def act_ranges(cfg: dict, params, calib) -> Dict[str, float]:
    """The calibration: each layer output's largest magnitude over the
    calibration rows, in float32."""
    def maxima(p, x):
        seen = {}

        def act(name, v):
            seen[name] = jnp.max(jnp.abs(v))
            return v
        model(cfg).forward(cfg, p, x, act)
        return seen
    with jax.default_matmul_precision("highest"):
        out = jax.jit(maxima)(params, calib)
    return {k: float(v) for k, v in out.items()}


def act_fracs(cfg: dict, ranges: Dict[str, float]) -> Dict[str, int]:
    """Fraction bits of each layer output's ``act_bits`` signed power-of-two
    grid that holds its calibrated ``[-max_abs, max_abs]``: integer bits
    ceil(log2(max_abs)), one sign bit, the rest fraction (the flow's
    fixed-point rule)."""
    bits = cfg["act_bits"]
    return {name: bits - 1 - math.ceil(math.log2(max(r, 1e-8) + 1e-12))
            for name, r in ranges.items()}


def weight_codes(cfg: dict, p: Dict[str, np.ndarray], layer: str,
                 bn: str | None = None) -> tuple:
    """``(codes, scale, bias)`` of ``layer`` at ``weight_bits`` from the
    NumPy weights ``p``: batch-norm ``bn`` folded in float64 then rounded
    to float32, then symmetric per-output-channel codes,
    ``scale = max|w| / (2^(bits-1) - 1)``."""
    q = 2 ** (cfg["weight_bits"] - 1) - 1
    w, b = p[f"{layer}/w"], p[f"{layer}/b"]
    if bn is not None:
        inv = (p[f"{bn}/scale"].astype(np.float64)
               / np.sqrt(p[f"{bn}/var"].astype(np.float64) + 1e-5))
        shift = p[f"{bn}/bias"] - p[f"{bn}/mean"] * inv
        w = (w.astype(np.float64) * inv).astype(np.float32)
        b = (b.astype(np.float64) * inv + shift).astype(np.float32)
    s = np.maximum(np.abs(w).max(axis=tuple(range(w.ndim - 1)),
                                 keepdims=True), np.float32(1e-8))
    s = (s / np.float32(q)).astype(np.float32)
    codes = np.clip(np.round(w / s), -q, q).astype(np.float32)
    return codes, s.reshape(-1), b


def grid_steps(fracs: Dict[str, int]) -> Dict[str, tuple]:
    """``(2^-frac, 2^frac)`` per layer output, as exact float32 scalars."""
    return {k: (np.float32(2.0 ** -f), np.float32(2.0 ** f))
            for k, f in fracs.items()}


_EXACT_K = 1024     # 1024 * 127 * 128 < 2^24: a float32 sum of codes is exact


def _dot_exact(x, w):
    """Integer-valued ``x @ w`` exactly: float32 partial sums over K chunks
    that stay below 2^24, added in int32."""
    acc = 0
    for k in range(0, x.shape[1], _EXACT_K):
        part = x[:, k:k + _EXACT_K] @ w[k:k + _EXACT_K]
        acc = acc + part.astype(jnp.int32)
    return acc.astype(jnp.float32)


class Codes:
    """The integer code domain of ``cfg`` with the layer codes ``qw``
    (:func:`quantized_weights`) and the grids ``steps``
    (:func:`grid_steps`).  The encode rule: a value goes onto its layer
    output's ``act_bits`` grid rounded half to even and saturated.  A layer
    accumulates codes x weight codes exactly, scales the sum by ``weight
    scale x input step``, adds its bias, applies its ReLU (if any) and is
    encoded.  Max-pool and ReLU act on codes."""

    def __init__(self, cfg: dict, qw: Dict[str, tuple], steps):
        self.lo = -2 ** (cfg["act_bits"] - 1)
        self.hi = 2 ** (cfg["act_bits"] - 1) - 1
        self.qw, self.steps = qw, steps

    def encode(self, v, name: str):
        return jnp.clip(jnp.round(v * self.steps[name][1]), self.lo, self.hi)

    def layer(self, c, src: str, name: str, relu: bool, stride: int = 1,
              groups: int = 1):
        """Conv layer ``name`` over the codes ``c`` of layer output
        ``src``."""
        codes, scale, bias = self.qw[name]
        acc = _conv(c, codes, 0.0, stride, groups)
        y = acc * (scale * self.steps[src][0]) + bias
        return self.encode(jax.nn.relu(y) if relu else y, name)

    def logits(self, c, src: str):
        """The ``fc`` layer over the flattened codes ``c`` of ``src``, its
        ``logits`` codes decoded to float."""
        codes, scale, bias = self.qw["fc"]
        acc = _dot_exact(c.reshape(c.shape[0], -1), codes)
        y = self.encode(acc * (scale * self.steps[src][0]) + bias, "logits")
        return y * self.steps["logits"][0]
