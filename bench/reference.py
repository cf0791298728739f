"""The plain float32 reference of every benchmark configuration, and the
seeded weights both it and the served program start from.

Nothing here imports the program: the weights are made by the benchmark (one
jitted call from the seed, on the device) and handed to the program's reader
and to :func:`forward` alike.  Parameter names follow the program's readers
(``conv0/w``, ``stem/w``, ``dw0/w``, ...), which is the only thing the two
share.

Two families, chosen by a configuration file's ``family`` key:

* ``cnn`` — conv(3x3, SAME) -> batch-norm -> ReLU -> maxpool per block, then
  flatten -> FC (the paper's Table II model);
* ``separable`` — conv stem(3x3, SAME) -> ReLU -> maxpool, then per block a
  depthwise conv(3x3, SAME, stride s) -> BN -> ReLU and a pointwise conv
  (1x1) -> BN -> ReLU, then flatten -> FC (MobileNetV1's body).

Batch-norm is the inference form with the stored statistics, which the
seeded weights set to identity (scale 1, bias 0, mean 0, var 1), folded into
its conv; every bias is zero, as a freshly initialised network has them.
With a positive batch-norm scale, max-pool commutes with batch-norm and ReLU,
so the ``cnn`` family pools after them.

:func:`quantized_forward` is the same model at the configuration's stated
precision (``act_bits``/``weight_bits``), computed in integer codes: what
the served program should compute, code for code, not merely approximate.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


def fc_in(cfg: dict) -> int:
    """Input width of the final FC layer (flattened last feature map)."""
    h, w = cfg["image_hw"]
    p = cfg["pool"]
    if cfg["family"] == "cnn":
        for _ in cfg["conv_channels"]:
            h, w = h // p, w // p
        return h * w * cfg["conv_channels"][-1]
    h, w = h // p, w // p
    for _, s in cfg["blocks"]:
        h, w = -(-h // s), -(-w // s)
    return h * w * cfg["blocks"][-1][0]


def _bn(params, layer: str, c: int) -> None:
    params[f"{layer}/scale"] = jnp.ones((c,), jnp.float32)
    params[f"{layer}/bias"] = jnp.zeros((c,), jnp.float32)
    params[f"{layer}/mean"] = jnp.zeros((c,), jnp.float32)
    params[f"{layer}/var"] = jnp.ones((c,), jnp.float32)


def _normal(key, shape, fan: int):
    return jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(float(fan))


def init_weights(cfg: dict, key) -> Dict[str, jax.Array]:
    """Seeded He-style weights for ``cfg`` (fan-in scaled normals)."""
    k = cfg["kernel_size"]
    cin = cfg["in_channels"]
    params: Dict[str, jax.Array] = {}
    if cfg["family"] == "cnn":
        keys = jax.random.split(key, len(cfg["conv_channels"]) + 1)
        for i, cout in enumerate(cfg["conv_channels"]):
            params[f"conv{i}/w"] = _normal(keys[i], (k, k, cin, cout),
                                           k * k * cin)
            params[f"conv{i}/b"] = jnp.zeros((cout,), jnp.float32)
            _bn(params, f"bn{i}", cout)
            cin = cout
    else:
        keys = jax.random.split(key, 2 * len(cfg["blocks"]) + 2)
        stem = cfg["stem_channels"]
        params["stem/w"] = _normal(keys[0], (k, k, cin, stem), k * k * cin)
        params["stem/b"] = jnp.zeros((stem,), jnp.float32)
        cin = stem
        for i, (cout, _) in enumerate(cfg["blocks"]):
            params[f"dw{i}/w"] = _normal(keys[2 * i + 1], (k, k, 1, cin), k * k)
            params[f"dw{i}/b"] = jnp.zeros((cin,), jnp.float32)
            params[f"pw{i}/w"] = _normal(keys[2 * i + 2], (1, 1, cin, cout),
                                         cin)
            params[f"pw{i}/b"] = jnp.zeros((cout,), jnp.float32)
            _bn(params, f"dw{i}_bn", cin)
            _bn(params, f"pw{i}_bn", cout)
            cin = cout
    n = fc_in(cfg)
    params["fc/w"] = _normal(keys[-1], (n, cfg["n_classes"]), n)
    params["fc/b"] = jnp.zeros((cfg["n_classes"],), jnp.float32)
    return params


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


def _forward(cfg: dict, params, x, act):
    """The model, with ``act(name, v)`` applied at every layer output.
    Layer outputs: ``input``; ``conv<i>``
    (conv + BN + ReLU, before its max-pool) for ``cnn``; ``stem`` (the stem
    conv, before its max-pool and ReLU), ``dw<i>`` and ``pw<i>`` (conv + BN
    + ReLU) for ``separable``; and ``logits``."""
    p = cfg["pool"]
    x = act("input", x)
    if cfg["family"] == "cnn":
        for i in range(len(cfg["conv_channels"])):
            w, b = _folded(params, f"conv{i}", f"bn{i}")
            x = act(f"conv{i}", jax.nn.relu(_conv(x, w, b)))
            x = _maxpool(x, p)
    else:
        x = act("stem", _conv(x, params["stem/w"], params["stem/b"]))
        x = jax.nn.relu(_maxpool(x, p))
        for i, (_, s) in enumerate(cfg["blocks"]):
            w, b = _folded(params, f"dw{i}", f"dw{i}_bn")
            x = act(f"dw{i}", jax.nn.relu(
                _conv(x, w, b, s, groups=x.shape[-1])))
            w, b = _folded(params, f"pw{i}", f"pw{i}_bn")
            x = act(f"pw{i}", jax.nn.relu(_conv(x, w, b)))
    x = x.reshape(x.shape[0], -1)
    return act("logits", x @ params["fc/w"] + params["fc/b"])


def forward(cfg: dict, params: Dict[str, jax.Array], x) -> jax.Array:
    """Float32 logits (B, n_classes) of images ``x`` (B, H, W, C).  Call it
    under ``jax.default_matmul_precision("highest")``: on a TPU a float32
    matmul or conv otherwise runs in bfloat16 passes."""
    return _forward(cfg, params, x, _same)


# -- the configuration's precision: D<act_bits> activations, W<bits> weights --

def act_ranges(cfg: dict, params, calib) -> Dict[str, float]:
    """The calibration: each layer output's largest magnitude over the
    calibration rows, in float32."""
    def maxima(p, x):
        seen = {}

        def act(name, v):
            seen[name] = jnp.max(jnp.abs(v))
            return v
        _forward(cfg, p, x, act)
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


def quantized_weights(cfg: dict, params) -> Dict[str, tuple]:
    """Per layer, ``(codes, scale, bias)`` at ``weight_bits``: batch-norm
    folded in float64 then rounded to float32, then symmetric
    per-output-channel codes, ``scale = max|w| / (2^(bits-1) - 1)``."""
    q = 2 ** (cfg["weight_bits"] - 1) - 1
    p = {k: np.asarray(v) for k, v in params.items()}

    def fold(layer, bn=None):
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

    out = {"fc": fold("fc")}
    if cfg["family"] == "cnn":
        for i in range(len(cfg["conv_channels"])):
            out[f"conv{i}"] = fold(f"conv{i}", f"bn{i}")
    else:
        out["stem"] = fold("stem")
        for i in range(len(cfg["blocks"])):
            out[f"dw{i}"] = fold(f"dw{i}", f"dw{i}_bn")
            out[f"pw{i}"] = fold(f"pw{i}", f"pw{i}_bn")
    return out


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


def quantized_forward(cfg: dict, qw: Dict[str, tuple], x, steps):
    """Logits of the model at the configuration's precision, in the integer
    code domain: the input and every layer output are ``act_bits`` codes on
    the power-of-two grids ``steps`` (:func:`grid_steps`); each layer
    accumulates codes x weight codes exactly, scales the sum by
    ``weight scale x input step``, adds its bias, applies its ReLU and
    rounds (half to even) and saturates to its output grid.  Max-pool and
    ReLU act on codes.  ``qw`` is :func:`quantized_weights`.  Call it under
    HIGHEST matmul precision: then every float32 sum of codes is exact."""
    lo, hi = -2 ** (cfg["act_bits"] - 1), 2 ** (cfg["act_bits"] - 1) - 1
    p = cfg["pool"]

    def encode(v, name):
        return jnp.clip(jnp.round(v * steps[name][1]), lo, hi)

    def layer(c, src, name, relu, stride=1, groups=1):
        codes, scale, bias = qw[name]
        acc = _conv(c, codes, 0.0, stride, groups)
        y = acc * (scale * steps[src][0]) + bias
        return encode(jax.nn.relu(y) if relu else y, name)

    c, src = encode(x, "input"), "input"
    if cfg["family"] == "cnn":
        for i in range(len(cfg["conv_channels"])):
            c, src = layer(c, src, f"conv{i}", True), f"conv{i}"
            c = _maxpool(c, p)
    else:
        c, src = layer(c, src, "stem", False), "stem"
        c = jax.nn.relu(_maxpool(c, p))
        for i, (_, s) in enumerate(cfg["blocks"]):
            c, src = layer(c, src, f"dw{i}", True, s, c.shape[-1]), f"dw{i}"
            c, src = layer(c, src, f"pw{i}", True), f"pw{i}"
    codes, scale, bias = qw["fc"]
    acc = _dot_exact(c.reshape(c.shape[0], -1), codes)
    y = encode(acc * (scale * steps[src][0]) + bias, "logits")
    return y * steps["logits"][0]
