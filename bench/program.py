"""The system under test, built the way a user builds it: seeded weights
through the program's reader, ``DesignFlow`` to the packed-weight ``qjax``
target at the configuration's precision, calibrated on seeded rows, and
served by ``FlowResult.serve_adaptive`` pinned to one working point.

This module builds and serves, and hands :mod:`run` the program's span
recorder (``spans``).  The only other code of the benchmark that touches
the program is each model module's ``to_ir`` (``bench/models/``), which
imports the program's reader inside the function: the configuration's IR
graph.
"""
from __future__ import annotations

import sys
from pathlib import Path

import jax
import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

from repro.caches import enable_compile_cache  # noqa: E402,F401 (for run.py)
from repro import spans  # noqa: E402,F401 (for run.py)
from repro.core.adaptive import FixedSelector, WorkingPoint  # noqa: E402
from repro.core.flow import DesignFlow  # noqa: E402
from repro.quant.qtypes import DatatypeConfig  # noqa: E402

import reference  # noqa: E402

# the working points the program serves, by name
POINTS = {"w8": WorkingPoint("w8", 8), "w4": WorkingPoint("w4", 4),
          "w2": WorkingPoint("w2", 2)}


def seed32(seed: int, stream: int) -> int:
    """A 32-bit key for ``jax.random`` from any whole-number seed (the
    benchmark's seeds may exceed 32 bits); ``stream`` separates uses."""
    return int(np.random.SeedSequence([seed % 2**63, stream]).generate_state(1)[0])


def make_weights(cfg: dict, seed: int):
    """The configuration's weights from ``seed``: one jitted call, on the
    device, in float32 (the type the flow reads them in)."""
    init = jax.jit(lambda key: reference.init_weights(cfg, key))
    return jax.block_until_ready(init(jax.random.PRNGKey(seed32(seed, 0))))


def image_shape(cfg: dict):
    return (*cfg["image_hw"], cfg["in_channels"])


def build(cfg: dict, params, calib, **writer_options):
    """``FlowResult`` of the ``qjax`` target at the configuration's
    precision, calibrated on the rows ``calib``.

    The flow calibrates on the float32 model.  On a TPU, JAX runs a float32
    matmul or convolution in one bfloat16 pass unless told otherwise, and
    the ranges it records then put some activations on another D8 grid
    (PERF.md).  So the flow is built under JAX's ``highest`` matmul
    precision, the float32 the configuration states.  The served program
    is integer; it is traced at its first call, after this returns."""
    graph = reference.model(cfg).to_ir(
        cfg, {k: np.asarray(v) for k, v in params.items()})
    with jax.default_matmul_precision(cfg["calibration_precision"]):
        return DesignFlow(graph).run(
            targets=("qjax",),
            dtconfig=DatatypeConfig(cfg["act_bits"], cfg["weight_bits"]),
            calib_inputs=(calib,),
            writer_kwargs={"qjax": writer_options} if writer_options else None)


def serve(result, point: str, server: dict):
    """The ``AccelServer`` the window drives: ``serve_adaptive`` over the one
    working point, pinned with ``FixedSelector``."""
    pt = POINTS[point]
    return result.serve_adaptive(
        points=(pt,), selector=FixedSelector(pt),
        max_batch=server["max_batch"], buckets=tuple(server["buckets"]),
        max_wait=server["max_wait_s"], pipeline_depth=server["pipeline_depth"],
        queue_depth=server["queue_depth"])
