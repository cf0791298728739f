"""Where the benchmark finds what belongs to one configuration, model family,
kernel or metric: a file named after it, in a directory of its kind.  A new
one arrives as a new file; no code lists them.

* ``configs/<name>.json`` — a configuration's sizes (``BENCHMARK.json``
  ``configs``);
* ``models/<family>.py`` — a model family (a configuration's ``family``):
  ``init_weights``, ``forward``, ``quantized_weights``,
  ``quantized_forward``, ``useful_ops_per_image`` and ``to_ir``
  (:mod:`reference`);
* ``kernels/<kernel>.py`` — ``count(operands, result) -> (ops, bytes)`` of
  one call of the Pallas kernel of that name (:mod:`work`);
* ``metrics/<name>.py`` — ``read(record)`` of a metric (:mod:`run`).

Each kind is looked up in the directories ``DIRS[kind]``, the first that
holds the file winning.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path
from types import ModuleType
from typing import Optional

BENCH = Path(__file__).resolve().parent
DIRS = {kind: [BENCH / kind]
        for kind in ("configs", "models", "kernels", "metrics")}


def path(kind: str, name: str, suffix: str = ".py") -> Optional[Path]:
    """The file of ``name`` of this kind, or None."""
    for d in DIRS[kind]:
        p = d / f"{name}{suffix}"
        if p.is_file():
            return p
    return None


def module(kind: str, name: str) -> Optional[ModuleType]:
    """``<kind>/<name>.py`` loaded as a module, or None if there is none."""
    p = path(kind, name)
    if p is None:
        return None
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
