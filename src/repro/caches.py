"""Where compiled programs and kernel tilings persist between processes.

Both caches live at fixed paths inside the checkout (``<repo>/.cache/``,
ignored by git): JAX keys its persistent compilation cache on the directory,
so a path that moves between runs never hits.  ``JAX_COMPILATION_CACHE_DIR``
(which JAX reads itself) and ``REPRO_AUTOTUNE_CACHE``
(:mod:`repro.kernels.autotune`) override them.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ROOT = Path(__file__).resolve().parents[2] / ".cache"
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.cache/jax``."""
    return os.environ.get(COMPILE_CACHE_ENV) or str(CACHE_ROOT / "jax")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache at :func:`compile_cache_dir`
    and returns that directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
    JAX already uses it and no directory is set here.  Every program is
    cached, however fast it compiled: the Pallas kernels and the autotuner's
    candidate tilings compile in well under JAX's default one-second floor."""
    if not os.environ.get(COMPILE_CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return compile_cache_dir()
