"""Production mesh definition (brief: MULTI-POD DRY-RUN step 1).

A function, not a module-level constant, so importing never touches jax
device state.  Single pod: 16x16 = 256 chips ("data", "model"); multi-pod:
2x16x16 = 512 chips ("pod", "data", "model").  Every axis is
``AxisType.Auto`` (sharding propagated by the compiler).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(model: int = 1):
    """Degenerate mesh over the locally available devices (smoke tests)."""
    n = len(jax.devices())
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
