"""jit'd public wrappers for the quantized matmul kernel.

``qmatmul(x, codes, scale, bits=…)`` handles arbitrary leading batch dims
and pads M/K/N up to MXU-aligned tiles, so every shape — a batch of one
included — runs the kernel.  ``qgemm`` is the float-activation
writer entry point: bias + ReLU + activation fake-quant fused into the kernel
epilogue.  ``qmatmul_int8_act`` is the *fully-integer* entry point: the
activation operand is the producer FIFO's int8 codes + a power-of-two scale,
MACs run in int32, and ``out_code=True`` re-quantizes the output to the
consumer's int8 code in the same epilogue — codes, not floats, flow between
layers.  Both accept ``packed=True`` to stream split-row sub-byte W4/W2
weight buffers (:func:`repro.quant.pack.pack_rows`) unpacked in-VMEM; the
packed path pads K to ``pack_align(bits)`` (128 lanes per packed activation
view) so every activation block is a whole number of lane tiles.

All entry points share backend-aware ``interpret`` selection (compiled on
TPU, interpret mode off-TPU; ``use_kernel=None`` runs the jnp reference
off-TPU) and a block-size autotune cache keyed on the padded problem.  The
autotune cache is two-level: the in-process dict is L1, and timed results
persist to a JSON file (:func:`repro.kernels.autotune.autotune_cache_path`)
so compiled-backend tuning survives across processes.
"""
from __future__ import annotations

import functools
import time
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import spans
from repro.kernels import autotune
from repro.kernels.qmatmul.kernel import (ActQt, build_call, DEFAULT_BM,
                                          DEFAULT_BN, DEFAULT_BK)
from repro.kernels.qmatmul.ref import (qgemm_ref, qmatmul_int8_act_ref,
                                       qmatmul_ref)
from repro.quant.pack import pack_align, unpack_rows

_MIN_TILE = 128

__all__ = ["qmatmul", "qgemm", "qmatmul_int8_act", "pick_blocks",
           "resolve_interpret", "ActQt"]


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Backend-aware ``interpret`` default: compiled Pallas on TPU, interpret
    mode everywhere else.  An explicit True/False always wins (writer kwargs
    pass it through for tests and forced modes)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


# -- block-size autotune ----------------------------------------------------
# keyed on the padded problem (M, K, N, bits, int8_act, packed) plus the
# interpret flag (an interpret-mode entry must not pin the untuned default
# for later compiled calls of the same shape); populated by timing candidate
# tilings on synthetic data the first time a shape is seen on a compiled
# backend, by the static default in interpret mode (timing interpret-mode
# Pallas would measure the emulator, not the hardware).  Timed entries are
# write-through persisted to the disk cache (see module docstring) and
# reloaded by later processes — the in-process dict stays the L1.
_BLOCK_CACHE: Dict[Tuple[int, int, int, int, bool, bool, bool],
                   Tuple[int, int, int]] = {}

_CANDIDATE_BLOCKS = ((128, 128, 512), (128, 256, 512), (256, 128, 512),
                     (128, 128, 256), (256, 256, 512))

# the disk half lives in repro.kernels.autotune (one versioned file shared
# by every kernel family); these aliases keep the historical module-level API
AUTOTUNE_CACHE_ENV = autotune.AUTOTUNE_CACHE_ENV
_disk_state = autotune._disk_state          # shared BY IDENTITY with autotune
autotune_cache_path = autotune.autotune_cache_path


def _disk_key(key) -> str:
    M, K, N, bits, int8_act, packed, _interp = key
    return f"{M}:{K}:{N}:{bits}:{int(int8_act)}:{int(packed)}"


def _disk_cache() -> Dict[str, Tuple[int, ...]]:
    return autotune.disk_cache()


def _disk_put(key, blocks: Tuple[int, int, int]) -> None:
    autotune.disk_put(_disk_key(key), blocks)


def _fit(dim: int, want: int, align: int) -> int:
    """Largest multiple of ``align`` that divides ``dim`` (itself a multiple
    of ``align``) and is no larger than ``max(want, align)``."""
    b = max(want - want % align, align)
    while dim % b:
        b -= align
    return b


def _k_align(bits: int, packed: bool) -> int:
    """K alignment of a padded problem: one lane tile, or one lane tile per
    packed activation view (``pack_align``) on the sub-byte path."""
    return pack_align(bits) if packed else _MIN_TILE


def _default_blocks(M: int, K: int, N: int,
                    k_align: int = _MIN_TILE) -> Tuple[int, int, int]:
    return (_fit(M, DEFAULT_BM, _MIN_TILE), _fit(N, DEFAULT_BN, _MIN_TILE),
            _fit(K, DEFAULT_BK, k_align))


def _fastest(cands, make_call: Callable, args):
    """The candidate whose call ``make_call(c)`` runs ``args`` fastest: one
    timing sweep, recorded as a ``kernels.autotune`` span and counted in
    ``kernels.autotune_sweeps`` while a span recorder is on."""
    rec = spans.active()
    t0 = 0 if rec is None else time.time_ns()
    best, best_t = None, float("inf")
    for c in sorted(cands):
        t = _time_call(make_call(c), args)
        if t < best_t:
            best, best_t = c, t
    if rec is not None:
        rec.add("kernels.autotune", t0, time.time_ns(), candidates=len(cands))
        rec.count("kernels.autotune_sweeps")
    return best


def _time_call(call, args, iters: int = 3) -> float:
    jax.block_until_ready(call(*args))          # compile + warm
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(call(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _synth_args(M: int, K: int, N: int, int8_act: bool, packed: bool,
                pack_ratio: int):
    """Concrete operands for the timing pass (shapes match the real call)."""
    if int8_act:
        x = jax.random.randint(jax.random.PRNGKey(0), (M, K), -127, 128,
                               jnp.int8)
    else:
        x = jax.random.normal(jax.random.PRNGKey(0), (M, K), jnp.bfloat16)
    if packed:
        w = jax.random.randint(jax.random.PRNGKey(1), (K // pack_ratio, N),
                               0, 256, jnp.int32).astype(jnp.uint8)
    else:
        w = jax.random.randint(jax.random.PRNGKey(1), (K, N), -127, 128,
                               jnp.int8)
    s = jnp.ones((1, N), jnp.float32)
    return [x] * pack_ratio + [w, s]


def pick_blocks(M: int, K: int, N: int, bits: int, interpret: bool,
                int8_act: bool = False,
                packed: bool = False) -> Tuple[int, int, int]:
    """(bm, bn, bk) for an M×K×N problem at a working point.

    M and N are already padded to multiples of ``_MIN_TILE``, K to
    ``_k_align(bits, packed)``; every candidate keeps ``bk`` a multiple of
    that alignment so each packed activation view is whole lane tiles.
    Results are cached per (M, K, N, bits, int8_act, packed, interpret); the
    timing pass runs on synthetic concrete data, so it is safe to call at
    trace time inside an outer jit.  Lookup order: in-process dict, then the on-disk
    cache (compiled-backend entries only), then a timing sweep whose result
    is written through to both."""
    key = (M, K, N, bits, int8_act, packed, interpret)
    hit = _BLOCK_CACHE.get(key)
    if hit is not None:
        return hit
    kal = _k_align(bits, packed)
    default = _default_blocks(M, K, N, kal)
    if interpret:
        _BLOCK_CACHE[key] = default
        return default
    disk = _disk_cache().get(_disk_key(key))
    if disk is not None and len(disk) == 3:
        _BLOCK_CACHE[key] = disk
        return disk
    r = (8 // bits) if packed else 1
    cands = {default}
    for bm, bn, bk in _CANDIDATE_BLOCKS:
        bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
        if M % bm == 0 and N % bn == 0 and K % bk == 0 and bk % kal == 0:
            cands.add((bm, bn, bk))
    if len(cands) == 1:
        _BLOCK_CACHE[key] = default
        return default
    args = _synth_args(M, K, N, int8_act, packed, r)
    best = _fastest(cands, lambda c: build_call(
        M, K, N, bits=bits, int8_act=int8_act, bm=c[0], bn=c[1], bk=c[2],
        interpret=False, packed=packed), args)
    _BLOCK_CACHE[key] = best
    _disk_put(key, best)
    return best


def _pad_to(x, m, axis):
    r = (-x.shape[axis]) % m
    if r == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, r)
    return jnp.pad(x, pads)


@functools.partial(jax.jit, static_argnames=("bits", "interpret", "use_kernel",
                                             "bm", "bn", "bk"))
def qmatmul(x, codes, scale, *, bits: int = 8,
            interpret: Optional[bool] = None,
            use_kernel: bool = True, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
            bk: int = DEFAULT_BK):
    """x: (..., K) float; codes: (K, N) int8; scale: (N,) f32 -> (..., N)."""
    lead = x.shape[:-1]
    K, N = codes.shape
    x2 = x.reshape(-1, K)
    M = x2.shape[0]
    if not use_kernel:
        y = qmatmul_ref(x2, codes, scale, bits, out_dtype=x.dtype)
        return y.reshape(*lead, N)
    interp = resolve_interpret(interpret)
    xp = _pad_to(_pad_to(x2, _MIN_TILE, 0), _MIN_TILE, 1)
    cp = _pad_to(_pad_to(codes, _MIN_TILE, 0), _MIN_TILE, 1)
    sp = _pad_to(scale.reshape(1, -1).astype(jnp.float32), _MIN_TILE, 1)
    (Mp, Kp), Np = xp.shape, cp.shape[1]
    call = build_call(Mp, Kp, Np, bits=bits, int8_act=False,
                      bm=_fit(Mp, bm, _MIN_TILE), bn=_fit(Np, bn, _MIN_TILE),
                      bk=_fit(Kp, bk, _MIN_TILE),
                      out_dtype=x.dtype, interpret=interp)
    y = call(xp.astype(jnp.bfloat16), cp, sp)[:M, :N]
    return y.reshape(*lead, N)


@functools.partial(jax.jit, static_argnames=("bits", "relu", "act_qt",
                                             "interpret", "use_kernel",
                                             "packed", "bm", "bn", "bk"))
def qgemm(x, codes, scale, bias=None, *, bits: int = 8, relu: bool = False,
          act_qt: Optional[ActQt] = None, interpret: Optional[bool] = None,
          use_kernel: Optional[bool] = None, packed: bool = False,
          bm: Optional[int] = None, bn: Optional[int] = None,
          bk: Optional[int] = None):
    """Packed-weight Gemm with the fused epilogue — the float-activation
    hot-path op.

    x: (..., K) float; codes: (K, N) int8 master — or, with ``packed=True``,
    the split-row sub-byte buffer (K'/r, N) uint8 where K' is K padded to
    ``pack_align(bits)`` (:func:`repro.quant.pack.pack_rows`); scale: (N,)
    f32; bias: (N,) or None.  ``use_kernel=None`` auto-selects: the compiled
    Pallas kernel on TPU, the jnp reference (which XLA constant-folds into a
    plain matmul when codes are trace constants) elsewhere.  ``act_qt`` is the
    consumer-side fixed-point activation quant ``(frac, qmin, qmax)``,
    applied inside the kernel epilogue instead of as a separate round/clip
    op per FIFO."""
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = codes.shape[-1]
    r = (8 // bits) if packed else 1
    if not packed:
        assert codes.shape[0] == K, (
            f"weight rows {codes.shape[0]} != reduction dim {K}")
    x2 = x.reshape(-1, K)
    M = x2.shape[0]
    interp = resolve_interpret(interpret)
    if use_kernel is None:
        use_kernel = not interp
    if not use_kernel:
        c = unpack_rows(codes, bits)[:K] if packed else codes
        y = qgemm_ref(x2, c, scale, bias, bits=bits, relu=relu,
                      act_qt=act_qt, out_dtype=x.dtype)
        return y.reshape(*lead, N)
    kal = _k_align(bits, packed)
    xp = _pad_to(_pad_to(x2, _MIN_TILE, 0), kal, 1)
    Mp, Kp = xp.shape
    if packed:
        assert codes.shape[0] * r == Kp, (
            f"packed weight rows {codes.shape[0]} (x{r}) do not cover the "
            f"padded reduction dim {Kp}")
        cp = _pad_to(codes, _MIN_TILE, 1)
        # the packed fields are q = view / step: fold the power-of-two step
        # into the channel scale (exact in f32)
        s_eff = scale.reshape(1, -1).astype(jnp.float32) * float(1 << (8 - bits))
    else:
        cp = _pad_to(_pad_to(codes, _MIN_TILE, 0), _MIN_TILE, 1)
        s_eff = scale.reshape(1, -1).astype(jnp.float32)
    Np = cp.shape[1]
    sp = _pad_to(s_eff, _MIN_TILE, 1)
    if bm is None or bn is None or bk is None:
        abm, abn, abk = pick_blocks(Mp, Kp, Np, bits, interp, packed=packed)
        bm, bn, bk = bm or abm, bn or abn, bk or abk
    args = [xp.astype(jnp.bfloat16)] * r + [cp, sp]
    if bias is not None:
        args.append(_pad_to(bias.reshape(1, -1).astype(jnp.float32),
                            _MIN_TILE, 1))
    call = build_call(Mp, Kp, Np, bits=bits, int8_act=False,
                      bm=_fit(Mp, bm, _MIN_TILE), bn=_fit(Np, bn, _MIN_TILE),
                      bk=_fit(Kp, bk, kal),
                      out_dtype=x.dtype, interpret=interp,
                      has_bias=bias is not None, relu=relu, act_qt=act_qt,
                      packed=packed)
    y = call(*args)[:M, :N]
    return y.reshape(*lead, N)


@functools.partial(jax.jit, static_argnames=("bits", "relu", "act_qt",
                                             "out_code", "packed", "interpret",
                                             "use_kernel", "out_dtype",
                                             "bm", "bn", "bk"))
def qmatmul_int8_act(x_codes, x_scale, codes, scale, bias=None, *,
                     bits: int = 8, relu: bool = False,
                     act_qt: Optional[ActQt] = None, out_code: bool = False,
                     packed: bool = False, interpret: Optional[bool] = None,
                     use_kernel: Optional[bool] = None,
                     out_dtype=jnp.bfloat16,
                     bm: Optional[int] = None, bn: Optional[int] = None,
                     bk: Optional[int] = None):
    """Fully-integer Gemm: x_codes (..., K) int8 activation codes, MACs in
    int32, the fused epilogue re-quantizing straight to the consumer's code.

    ``x_scale`` is the producer FIFO's activation scale — a scalar (the hot
    path: a power of two from calibration, folded into the per-channel weight
    scale with zero extra work) or per-row ``(M,)`` (the legacy dynamic-range
    path, applied in the epilogue).  ``codes`` is (K, N) int8 or the
    split-row packed (K'/r, N) uint8 buffer with ``packed=True``;
    ``out_code=True`` returns int8 codes (``act_qt`` required), else the
    dequantized float in ``out_dtype``."""
    lead = x_codes.shape[:-1]
    K = x_codes.shape[-1]
    N = codes.shape[-1]
    r = (8 // bits) if packed else 1
    if not packed:
        assert codes.shape[0] == K, (
            f"weight rows {codes.shape[0]} != reduction dim {K}")
    x2 = x_codes.reshape(-1, K)
    M = x2.shape[0]
    xs = jnp.asarray(x_scale, jnp.float32)
    per_row = xs.ndim >= 1 and xs.size > 1
    interp = resolve_interpret(interpret)
    if use_kernel is None:
        use_kernel = not interp
    if not use_kernel:
        c = unpack_rows(codes, bits)[:K] if packed else codes
        y = qmatmul_int8_act_ref(x2, xs, c, scale, bits, bias=bias, relu=relu,
                                 act_qt=act_qt, out_code=out_code,
                                 out_dtype=out_dtype)
        return y.reshape(*lead, N)
    kal = _k_align(bits, packed)
    xp = _pad_to(_pad_to(x2, _MIN_TILE, 0), kal, 1)
    Mp, Kp = xp.shape
    if packed:
        assert codes.shape[0] * r == Kp, (
            f"packed weight rows {codes.shape[0]} (x{r}) do not cover the "
            f"padded reduction dim {Kp}")
        cp = _pad_to(codes, _MIN_TILE, 1)
        s_eff = scale.reshape(1, -1).astype(jnp.float32) * float(1 << (8 - bits))
    else:
        cp = _pad_to(_pad_to(codes, _MIN_TILE, 0), _MIN_TILE, 1)
        s_eff = scale.reshape(1, -1).astype(jnp.float32)
    Np = cp.shape[1]
    if not per_row:
        # scalar activation scale: fold into the channel scale (bit-exact
        # with the oracle's fold — both scales are powers of two)
        s_eff = s_eff * xs.reshape(())
    sp = _pad_to(s_eff, _MIN_TILE, 1)
    if bm is None or bn is None or bk is None:
        abm, abn, abk = pick_blocks(Mp, Kp, Np, bits, interp, int8_act=True,
                                    packed=packed)
        bm, bn, bk = bm or abm, bn or abn, bk or abk
    args = [xp] * r
    if per_row:
        args.append(_pad_to(xs.reshape(-1, 1), _MIN_TILE, 0))
    args += [cp, sp]
    if bias is not None:
        args.append(_pad_to(bias.reshape(1, -1).astype(jnp.float32),
                            _MIN_TILE, 1))
    call = build_call(Mp, Kp, Np, bits=bits, int8_act=True,
                      bm=_fit(Mp, bm, _MIN_TILE), bn=_fit(Np, bn, _MIN_TILE),
                      bk=_fit(Kp, bk, kal),
                      out_dtype=out_dtype, interpret=interp,
                      has_bias=bias is not None, relu=relu, act_qt=act_qt,
                      packed=packed, emit_code=out_code, has_xscale=per_row)
    y = call(*args)[:M, :N]
    return y.reshape(*lead, N)
