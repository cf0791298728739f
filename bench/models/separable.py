"""The ``separable`` family: conv stem(3x3, SAME) -> ReLU -> maxpool, then
per block a depthwise conv(3x3, SAME, stride s) -> BN -> ReLU and a
pointwise conv (1x1) -> BN -> ReLU, then flatten -> FC (MobileNetV1's body,
arXiv:1704.04861).

Configuration keys: ``image_hw``, ``in_channels``, ``stem_channels``,
``blocks`` (``[out_channels, depthwise stride]`` each), ``kernel_size``,
``pool``, ``n_classes``.  Layer outputs (the names ``forward``'s ``act``
sees and the activation grids are keyed by): ``input``, ``stem`` (the stem
conv, before its max-pool and ReLU), ``dw<i>`` and ``pw<i>`` (conv + BN +
ReLU) and ``logits``.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from reference import (Codes, _bn, _conv, _folded, _maxpool, _normal,
                       weight_codes)


def fc_in(cfg: dict) -> int:
    """Input width of the final FC layer (flattened last feature map)."""
    h, w = cfg["image_hw"]
    p = cfg["pool"]
    h, w = h // p, w // p
    for _, s in cfg["blocks"]:
        h, w = -(-h // s), -(-w // s)
    return h * w * cfg["blocks"][-1][0]


def init_weights(cfg: dict, key) -> Dict[str, jax.Array]:
    k = cfg["kernel_size"]
    cin = cfg["in_channels"]
    params: Dict[str, jax.Array] = {}
    keys = jax.random.split(key, 2 * len(cfg["blocks"]) + 2)
    stem = cfg["stem_channels"]
    params["stem/w"] = _normal(keys[0], (k, k, cin, stem), k * k * cin)
    params["stem/b"] = jnp.zeros((stem,), jnp.float32)
    cin = stem
    for i, (cout, _) in enumerate(cfg["blocks"]):
        params[f"dw{i}/w"] = _normal(keys[2 * i + 1], (k, k, 1, cin), k * k)
        params[f"dw{i}/b"] = jnp.zeros((cin,), jnp.float32)
        params[f"pw{i}/w"] = _normal(keys[2 * i + 2], (1, 1, cin, cout), cin)
        params[f"pw{i}/b"] = jnp.zeros((cout,), jnp.float32)
        _bn(params, f"dw{i}_bn", cin)
        _bn(params, f"pw{i}_bn", cout)
        cin = cout
    n = fc_in(cfg)
    params["fc/w"] = _normal(keys[-1], (n, cfg["n_classes"]), n)
    params["fc/b"] = jnp.zeros((cfg["n_classes"],), jnp.float32)
    return params


def forward(cfg: dict, params, x, act):
    x = act("input", x)
    x = act("stem", _conv(x, params["stem/w"], params["stem/b"]))
    x = jax.nn.relu(_maxpool(x, cfg["pool"]))
    for i, (_, s) in enumerate(cfg["blocks"]):
        w, b = _folded(params, f"dw{i}", f"dw{i}_bn")
        x = act(f"dw{i}", jax.nn.relu(_conv(x, w, b, s, groups=x.shape[-1])))
        w, b = _folded(params, f"pw{i}", f"pw{i}_bn")
        x = act(f"pw{i}", jax.nn.relu(_conv(x, w, b)))
    x = x.reshape(x.shape[0], -1)
    return act("logits", x @ params["fc/w"] + params["fc/b"])


def quantized_weights(cfg: dict, params) -> Dict[str, tuple]:
    p = {k: np.asarray(v) for k, v in params.items()}
    out = {"fc": weight_codes(cfg, p, "fc"),
           "stem": weight_codes(cfg, p, "stem")}
    for i in range(len(cfg["blocks"])):
        out[f"dw{i}"] = weight_codes(cfg, p, f"dw{i}", f"dw{i}_bn")
        out[f"pw{i}"] = weight_codes(cfg, p, f"pw{i}", f"pw{i}_bn")
    return out


def quantized_forward(cfg: dict, qw: Dict[str, tuple], x, steps):
    q = Codes(cfg, qw, steps)
    c, src = q.encode(x, "input"), "input"
    c, src = q.layer(c, src, "stem", False), "stem"
    c = jax.nn.relu(_maxpool(c, cfg["pool"]))
    for i, (_, s) in enumerate(cfg["blocks"]):
        c, src = q.layer(c, src, f"dw{i}", True, s, c.shape[-1]), f"dw{i}"
        c, src = q.layer(c, src, f"pw{i}", True), f"pw{i}"
    return q.logits(c, src)


def useful_ops_per_image(cfg: dict) -> int:
    """2 x the multiply-accumulates of every conv and FC layer (SAME
    padding: output size is ceil(input / stride))."""
    h, w = cfg["image_hw"]
    k = cfg["kernel_size"]
    cin = cfg["in_channels"]
    macs = h * w * k * k * cin * cfg["stem_channels"]
    h, w, cin = h // cfg["pool"], w // cfg["pool"], cfg["stem_channels"]
    for cout, s in cfg["blocks"]:
        h, w = -(-h // s), -(-w // s)
        macs += h * w * k * k * cin          # depthwise
        macs += h * w * cin * cout           # pointwise
        cin = cout
    macs += fc_in(cfg) * cfg["n_classes"]
    return 2 * macs


def to_ir(cfg: dict, params: Dict[str, np.ndarray]):
    """The program's IR graph of ``cfg`` with ``params``, from its reader."""
    from repro.configs.separable_cnn import SeparableCNNConfig
    from repro.core.reader import separable_cnn_to_ir
    pcfg = SeparableCNNConfig(
        name=cfg["name"], image_hw=tuple(cfg["image_hw"]),
        in_channels=cfg["in_channels"], stem_channels=cfg["stem_channels"],
        blocks=tuple(tuple(b) for b in cfg["blocks"]),
        kernel_size=cfg["kernel_size"], pool=cfg["pool"],
        n_classes=cfg["n_classes"])
    return separable_cnn_to_ir(pcfg, params)
