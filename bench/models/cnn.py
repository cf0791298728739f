"""The ``cnn`` family: conv(3x3, SAME) -> batch-norm -> ReLU -> maxpool per
block, then flatten -> FC (arXiv:2309.13321, Table II).  With a positive
batch-norm scale, max-pool commutes with batch-norm and ReLU, so the model
pools after them.

Configuration keys: ``image_hw``, ``in_channels``, ``conv_channels``,
``kernel_size``, ``pool``, ``n_classes``.  Layer outputs (the names
``forward``'s ``act`` sees and the activation grids are keyed by):
``input``, ``conv<i>`` (conv + BN + ReLU, before its max-pool) and
``logits``.
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
    for _ in cfg["conv_channels"]:
        h, w = h // p, w // p
    return h * w * cfg["conv_channels"][-1]


def init_weights(cfg: dict, key) -> Dict[str, jax.Array]:
    k = cfg["kernel_size"]
    cin = cfg["in_channels"]
    params: Dict[str, jax.Array] = {}
    keys = jax.random.split(key, len(cfg["conv_channels"]) + 1)
    for i, cout in enumerate(cfg["conv_channels"]):
        params[f"conv{i}/w"] = _normal(keys[i], (k, k, cin, cout),
                                       k * k * cin)
        params[f"conv{i}/b"] = jnp.zeros((cout,), jnp.float32)
        _bn(params, f"bn{i}", cout)
        cin = cout
    n = fc_in(cfg)
    params["fc/w"] = _normal(keys[-1], (n, cfg["n_classes"]), n)
    params["fc/b"] = jnp.zeros((cfg["n_classes"],), jnp.float32)
    return params


def forward(cfg: dict, params, x, act):
    p = cfg["pool"]
    x = act("input", x)
    for i in range(len(cfg["conv_channels"])):
        w, b = _folded(params, f"conv{i}", f"bn{i}")
        x = act(f"conv{i}", jax.nn.relu(_conv(x, w, b)))
        x = _maxpool(x, p)
    x = x.reshape(x.shape[0], -1)
    return act("logits", x @ params["fc/w"] + params["fc/b"])


def quantized_weights(cfg: dict, params) -> Dict[str, tuple]:
    p = {k: np.asarray(v) for k, v in params.items()}
    out = {"fc": weight_codes(cfg, p, "fc")}
    for i in range(len(cfg["conv_channels"])):
        out[f"conv{i}"] = weight_codes(cfg, p, f"conv{i}", f"bn{i}")
    return out


def quantized_forward(cfg: dict, qw: Dict[str, tuple], x, steps):
    q = Codes(cfg, qw, steps)
    c, src = q.encode(x, "input"), "input"
    for i in range(len(cfg["conv_channels"])):
        c, src = q.layer(c, src, f"conv{i}", True), f"conv{i}"
        c = _maxpool(c, cfg["pool"])
    return q.logits(c, src)


def useful_ops_per_image(cfg: dict) -> int:
    """2 x the multiply-accumulates of every conv and FC layer."""
    h, w = cfg["image_hw"]
    k = cfg["kernel_size"]
    cin = cfg["in_channels"]
    macs = 0
    for cout in cfg["conv_channels"]:
        macs += h * w * k * k * cin * cout
        h, w, cin = h // cfg["pool"], w // cfg["pool"], cout
    macs += fc_in(cfg) * cfg["n_classes"]
    return 2 * macs


def to_ir(cfg: dict, params: Dict[str, np.ndarray]):
    """The program's IR graph of ``cfg`` with ``params``, from its reader."""
    from repro.configs.mnist_cnn import CNNConfig
    from repro.core.reader import cnn_to_ir
    pcfg = CNNConfig(name=cfg["name"], image_hw=tuple(cfg["image_hw"]),
                     in_channels=cfg["in_channels"],
                     conv_channels=tuple(cfg["conv_channels"]),
                     kernel_size=cfg["kernel_size"], pool=cfg["pool"],
                     n_classes=cfg["n_classes"])
    return cnn_to_ir(pcfg, params)
