"""Persistent block-size autotune cache shared by the Pallas kernel families.

Timed tiling picks are two-level cached: each kernel family keeps its own
in-process L1 dict, and compiled-backend timings persist here to ONE JSON
file (``<repo>/.cache/autotune.json`` beside the compilation cache of
:mod:`repro.caches`, override with ``REPRO_AUTOTUNE_CACHE=<path>``, disable
with ``REPRO_AUTOTUNE_CACHE=off``) so tuning survives across processes.
Keys are family-prefixed strings (``"512:384:..."`` for qmatmul, ``"dw:..."``
for the depthwise conv kernels) and values are integer block tuples of
*family-specific arity*.

The file carries an explicit schema version::

    {"schema": 2, "entries": {"<key>": [<blocks...>], ...}}

Any file whose schema does not match :data:`CACHE_SCHEMA` — including the
pre-versioned flat ``{key: blocks}`` format older releases wrote — is treated
as empty, so stale caches *retune* instead of silently returning block tuples
of the wrong arity to a newer kernel.  Bump :data:`CACHE_SCHEMA` whenever a
key format or tuple arity changes.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Optional, Tuple

from repro.caches import CACHE_ROOT

# bump on any key-format or block-tuple-arity change; mismatched (or
# pre-versioned) files are discarded and retuned
CACHE_SCHEMA = 2

AUTOTUNE_CACHE_ENV = "REPRO_AUTOTUNE_CACHE"


class CacheFormatError(ValueError):
    """Typed validation failure for autotune-cache entries: block tuples
    must be non-empty sequences of positive integers (bool is not an int
    here, and floats/NaN/negatives are rejected) — a corrupted block pick
    would otherwise propagate straight into Pallas grid shapes."""


def _valid_blocks(v: object) -> Tuple[int, ...]:
    """Validate one cache value; raises :class:`CacheFormatError`."""
    if not isinstance(v, (list, tuple)) or len(v) < 1:
        raise CacheFormatError(
            f"cache entry must be a non-empty block list, got {v!r}")
    blocks = []
    for b in v:
        if isinstance(b, bool) or not isinstance(b, int) or b <= 0:
            raise CacheFormatError(
                f"block sizes must be positive integers, got {b!r} in {v!r}")
        blocks.append(int(b))
    return tuple(blocks)

# loaded disk state: {"path": resolved path or None, "data": {key: blocks}};
# re-resolved when the env var changes (tests point it at tmp dirs).  The
# dict OBJECT is shared by identity with the per-family ops modules.
_disk_state: Dict[str, object] = {"path": False, "data": {}}


def autotune_cache_path() -> Optional[str]:
    """Resolved disk-cache path, or None when persistence is disabled."""
    p = os.environ.get(AUTOTUNE_CACHE_ENV)
    if p is None:
        return str(CACHE_ROOT / "autotune.json")
    p = p.strip()
    if p.lower() in ("", "0", "off", "none"):
        return None
    return os.path.expanduser(p)


def disk_cache() -> Dict[str, Tuple[int, ...]]:
    """The persisted ``{key: blocks}`` map (empty when disabled, corrupt, or
    written under a different :data:`CACHE_SCHEMA`)."""
    path = autotune_cache_path()
    if _disk_state["path"] != path:
        data: Dict[str, Tuple[int, ...]] = {}
        if path is not None and os.path.exists(path):
            try:
                with open(path) as f:
                    raw = json.load(f)
                # schema gate: flat pre-versioned files and future formats
                # both load as empty -> retune rather than mis-shape blocks
                if isinstance(raw, dict) and raw.get("schema") == CACHE_SCHEMA:
                    entries = raw.get("entries", {})
                    if not isinstance(entries, dict):
                        raise CacheFormatError(
                            f"'entries' must be a dict, got "
                            f"{type(entries).__name__}")
                    for k, v in entries.items():
                        # per-entry validation: one corrupted pick retunes
                        # that key; the rest of the cache stays usable
                        try:
                            data[str(k)] = _valid_blocks(v)
                        except CacheFormatError:
                            continue
            except (OSError, ValueError, TypeError):
                data = {}   # corrupt/unreadable cache: retune, then rewrite
        _disk_state["path"] = path
        _disk_state["data"] = data
    return _disk_state["data"]  # type: ignore[return-value]


def tuned_entries(prefix: str = "") -> Dict[str, Tuple[int, ...]]:
    """Snapshot of the persisted tuned tilings whose key starts with
    ``prefix`` (``""`` = all families; ``"dw:"`` = the depthwise kernels).

    The DSE reads this to report which candidate shapes already carry a
    *timed* block pick — a tuned tiling means the measured-latency term for
    that shape is grounded in a real kernel timing rather than the static
    heuristic."""
    return {k: tuple(v) for k, v in disk_cache().items()
            if k.startswith(prefix)}


def disk_put(key: str, blocks: Tuple[int, ...]) -> None:
    """Write-through one timed result (no-op when persistence is off)."""
    path = autotune_cache_path()
    if path is None:
        return
    data = disk_cache()
    # strict on the write side: persisting a garbage pick poisons every
    # later process, so it fails loudly (typed) instead of best-effort
    data[key] = _valid_blocks(blocks)
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"schema": CACHE_SCHEMA,
                       "entries": {k: list(v) for k, v in sorted(data.items())}},
                      f, indent=1)
        os.replace(tmp, path)   # atomic: concurrent tuners never see partials
    except OSError:
        pass                    # telemetry-grade persistence: never fail a call
