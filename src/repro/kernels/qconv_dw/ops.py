"""jit'd public wrappers for the direct depthwise conv kernel.

``qconv_dw(x, codes, scale, …)`` is the float-activation entry point and
``qconv_dw_int8_act`` the fully-integer one: int8 activation codes in, int32
window MACs, and ``out_code=True`` re-quantizes straight to the consumer's
int8 code in the fused epilogue — the depthwise stage of a separable block
never leaves the code domain.  Both accept ``packed=True`` to stream the
split-row sub-byte W4/W2 weight buffer (:func:`repro.quant.pack.pack_rows`
at ``align=DW_PACK_ALIGN`` — a 3x3 window packs its 9 tap rows into 16, not
the matmul tile's 128) unpacked in-VMEM.

Host-side prep pads the spatial window so every tap slice stays in bounds
and the W lane dim tiles cleanly, orders each row's columns by phase at a
column stride > 1 (so every tap is a unit-stride slice), then hands the
kernel ``kh`` row-shifted *views* of one padded activation array — the
patch tensor of the legacy im2col + qgemm lowering is never materialized.
Autotuning picks the channel block the same way qmatmul picks (bm, bn, bk):
in-process L1 dict, then the shared versioned disk cache
(``repro.kernels.autotune``) under ``"dw:"``-prefixed keys, then a timing
sweep on compiled backends.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import spans
from repro.kernels import autotune
from repro.kernels.qconv_dw.kernel import DEFAULT_BC, build_dw_call
from repro.kernels.qconv_dw.ref import (ActQt, normalize_pads, out_spatial,
                                        qconv_dw_int8_act_ref, qconv_dw_ref)
from repro.kernels.qmatmul.ops import _fastest, _pad_to, resolve_interpret
from repro.quant.pack import unpack_rows

# split-row packing alignment for depthwise tap rows: the reduction is kh*kw
# (9 for a 3x3 window), so aligning to the matmul tile's 128 would store 93%
# padding — 8 keeps the sub-byte byte counts honest and still divides by
# every pack ratio
DW_PACK_ALIGN = 8

_LANE = 128

__all__ = ["qconv_dw", "qconv_dw_int8_act", "pick_blocks_dw",
           "DW_PACK_ALIGN", "ActQt"]


def _round_up(n: int, m: int) -> int:
    return n + (-n) % m


# -- channel-block autotune -------------------------------------------------
# same two-level scheme as qmatmul.ops.pick_blocks, tuning the single free
# tiling knob of the depthwise grid (the channel block bc); entries share
# qmatmul's disk file under family-prefixed "dw:" keys, stored as 1-tuples
_BC_CACHE: Dict[tuple, int] = {}

_CANDIDATE_BC = (128, 256, 512)


def _disk_key_dw(B: int, oh: int, Wpp: int, Cp: int, kh: int, kw: int,
                 sh: int, sw: int, bits: int, int8_act: bool,
                 packed: bool) -> str:
    return (f"dw:{B}:{oh}:{Wpp}:{Cp}:{kh}x{kw}:{sh}{sw}:{bits}:"
            f"{int(int8_act)}:{int(packed)}")


def _synth_dw_args(B: int, Hp: int, Wpp: int, Cp: int, kh: int, w_rows: int,
                   int8_act: bool, packed: bool):
    """Concrete operands for the timing pass (shapes match the real call)."""
    if int8_act:
        x = jax.random.randint(jax.random.PRNGKey(0), (B * Hp, Wpp, Cp),
                               -127, 128, jnp.int8)
    else:
        x = jax.random.normal(jax.random.PRNGKey(0), (B * Hp, Wpp, Cp),
                              jnp.float32)
    if packed:
        w = jax.random.randint(jax.random.PRNGKey(1), (w_rows, Cp),
                               0, 256, jnp.int32).astype(jnp.uint8)
    else:
        w = jax.random.randint(jax.random.PRNGKey(1), (w_rows, Cp),
                               -127, 128, jnp.int8)
    return [x] * kh + [w, jnp.ones((1, Cp), jnp.float32)]


def pick_blocks_dw(B: int, Hp: int, Wpp: int, Cp: int, *, kh: int, kw: int,
                   sh: int, sw: int, oh: int, ow: int, w_rows: int, bits: int,
                   interpret: bool, int8_act: bool = False,
                   packed: bool = False) -> int:
    """Channel block ``bc`` for a padded depthwise problem at a working point.

    Interpret mode takes the static default without timing (timing the
    emulator would tune for the wrong machine); compiled backends sweep the
    divisor candidates once per shape and write the winner through to the
    shared disk cache."""
    key = ("dw", B, oh, Wpp, Cp, kh, kw, sh, sw, bits, int8_act, packed,
           interpret)
    hit = _BC_CACHE.get(key)
    if hit is not None:
        return hit
    default = min(DEFAULT_BC, Cp)
    if interpret:
        _BC_CACHE[key] = default
        return default
    dk = _disk_key_dw(B, oh, Wpp, Cp, kh, kw, sh, sw, bits, int8_act, packed)
    disk = autotune.disk_cache().get(dk)
    if disk is not None and len(disk) == 1:
        _BC_CACHE[key] = disk[0]
        return disk[0]
    cands = {default} | {c for c in _CANDIDATE_BC if Cp % c == 0}
    if len(cands) == 1:
        _BC_CACHE[key] = default
        return default
    args = _synth_dw_args(B, Hp, Wpp, Cp, kh, w_rows, int8_act, packed)
    best = _fastest(cands, lambda bc: build_dw_call(
        B, Hp, Wpp, Cp, kh=kh, kw=kw, sh=sh, sw=sw, oh=oh, ow=ow,
        w_rows=w_rows, bits=bits, int8_act=int8_act, bc=bc, interpret=False,
        packed=packed), args)
    _BC_CACHE[key] = best
    autotune.disk_put(dk, (best,))
    return best


def _prep_spatial(x, hpad, wpad, kw: int, sw: int, ow: int):
    """Pad a (B, H, W, C) activation by the window's ``hpad``/``wpad`` and on
    to the kernel's tiling; returns (x2, Hp, Wpp, Cp, owp) with x2 in the
    (B*Hp, Wpp, Cp) row-view layout.

    At ``sw > 1`` each padded row's columns are also ordered by phase:
    column ``c`` moves to ``(c % sw) * (Wpp // sw) + c // sw``, so the
    columns ``dx + sw*o`` of tap ``dx`` are one contiguous slice
    (``kernel.phase_offset``).  ``Wpp`` is then a multiple of ``8 * sw``, so
    every phase starts on a sublane tile.  Each phase is a strided slice of
    ``x``, padded on its own: on the TPU, XLA makes that ``sw`` slices and one
    pad fusion, where a reshape + transpose of the padded array costs a
    whole extra copy of it."""
    B, H, W, C = x.shape
    Hp, Wp = H + sum(hpad), W + sum(wpad)
    owp = _round_up(ow, 8)
    wpp = _round_up(max(Wp, (kw - 1) + sw * owp), 8 * sw)
    cp = _round_up(C, _LANE)
    if sw == 1:
        xp = jnp.pad(x, ((0, 0), hpad, (wpad[0], wpp - W - wpad[0]),
                         (0, cp - C)))
        return xp.reshape(B * Hp, wpp, cp), Hp, wpp, cp, owp
    rec = spans.active()
    if rec is not None:
        rec.count("kernels.dw_phase_views")
    wq, left = wpp // sw, wpad[0]
    phases = []
    for p in range(sw):
        k0 = max(0, -(-(left - p) // sw))       # first phase column inside x
        c0 = p + sw * k0 - left                 # ... and its column of x
        n = len(range(c0, W, sw))
        cols = jax.lax.slice(x, (0, 0, min(c0, W), 0), (B, H, W, C),
                             (1, 1, sw, 1))
        phases.append(jnp.pad(cols, ((0, 0), hpad, (k0, wq - k0 - n),
                                     (0, cp - C))))
    xp = jnp.concatenate(phases, axis=2)
    return xp.reshape(B * Hp, wpp, cp), Hp, wpp, cp, owp


def _prep_weights(codes, scale, bias, k2: int, cp: int, bits: int,
                  packed: bool):
    """(w, sp, bp, w_rows) padded to the channel tile; sub-byte step folded
    into the scale on the packed path (exact: the step is a power of two)."""
    if packed:
        r = 8 // bits
        assert codes.shape[0] * r == _round_up(k2, DW_PACK_ALIGN), (
            f"packed tap rows {codes.shape[0]} (x{r}) do not cover the "
            f"aligned window {_round_up(k2, DW_PACK_ALIGN)}")
        w = _pad_to(codes, cp, 1)
        s_eff = scale.reshape(1, -1).astype(jnp.float32) * float(1 << (8 - bits))
    else:
        assert codes.shape[0] == k2, (
            f"weight tap rows {codes.shape[0]} != window size {k2}")
        w = _pad_to(_pad_to(codes, 8, 0), cp, 1)
        s_eff = scale.reshape(1, -1).astype(jnp.float32)
    sp = _pad_to(s_eff, cp, 1)
    bp = None
    if bias is not None:
        bp = _pad_to(bias.reshape(1, -1).astype(jnp.float32), cp, 1)
    return w, sp, bp, w.shape[0]


@functools.partial(jax.jit, static_argnames=(
    "kh", "kw", "strides", "pads", "bits", "relu", "act_qt", "interpret",
    "use_kernel", "packed", "bc"))
def qconv_dw(x, codes, scale, bias=None, *, kh: int, kw: int,
             strides: Tuple[int, int] = (1, 1), pads="SAME", bits: int = 8,
             relu: bool = False, act_qt: Optional[ActQt] = None,
             interpret: Optional[bool] = None,
             use_kernel: Optional[bool] = None, packed: bool = False,
             bc: Optional[int] = None):
    """Float-activation direct depthwise conv with the fused epilogue.

    x: (B, H, W, C) float NHWC; codes: (kh*kw, C) int8 master tap rows — or,
    with ``packed=True``, the split-row sub-byte buffer
    (align(kh*kw, 8)/r, C) uint8; scale: (C,) f32; bias: (C,) or None.
    ``pads`` must be hashable: "SAME" / "VALID" or the normalized
    ((top, bottom), (left, right)) from :func:`normalize_pads`."""
    B, H, W, C = x.shape
    k2 = kh * kw
    interp = resolve_interpret(interpret)
    if use_kernel is None:
        use_kernel = not interp
    if not use_kernel:
        c = unpack_rows(codes, bits)[:k2] if packed else codes
        return qconv_dw_ref(x, c, scale, bias, kh=kh, kw=kw, strides=strides,
                            pads=pads, bits=bits, relu=relu, act_qt=act_qt,
                            out_dtype=x.dtype)
    sh, sw = strides
    oh, ow, hpad, wpad = out_spatial(H, W, kh, kw, strides, pads)
    # f32 in the window MACs (not bf16): fixed-point activations make every
    # tap product exact, leaving only epilogue fma-contraction ulps vs the
    # oracle (qmatmul's float path loses bf16 mantissa bits in the MXU)
    x2, Hp, wpp, cp, owp = _prep_spatial(x.astype(jnp.float32), hpad, wpad,
                                         kw, sw, ow)
    w, sp, bp, w_rows = _prep_weights(codes, scale, bias, k2, cp, bits, packed)
    if bc is None:
        bc = pick_blocks_dw(B, Hp, wpp, cp, kh=kh, kw=kw, sh=sh, sw=sw,
                            oh=oh, ow=owp, w_rows=w_rows, bits=bits,
                            interpret=interp, packed=packed)
    call = build_dw_call(B, Hp, wpp, cp, kh=kh, kw=kw, sh=sh, sw=sw, oh=oh,
                         ow=owp, w_rows=w_rows, bits=bits, int8_act=False,
                         bc=bc, out_dtype=x.dtype, interpret=interp,
                         has_bias=bias is not None, relu=relu, act_qt=act_qt,
                         packed=packed)
    args = [x2] * kh + [w, sp] + ([bp] if bp is not None else [])
    y = call(*args)
    return y.reshape(B, oh, owp, cp)[:, :, :ow, :C]


@functools.partial(jax.jit, static_argnames=(
    "kh", "kw", "strides", "pads", "bits", "relu", "act_qt", "out_code",
    "packed", "interpret", "use_kernel", "out_dtype", "bc"))
def qconv_dw_int8_act(x_codes, x_scale, codes, scale, bias=None, *, kh: int,
                      kw: int, strides: Tuple[int, int] = (1, 1),
                      pads="SAME", bits: int = 8, relu: bool = False,
                      act_qt: Optional[ActQt] = None, out_code: bool = False,
                      packed: bool = False, interpret: Optional[bool] = None,
                      use_kernel: Optional[bool] = None,
                      out_dtype=jnp.float32, bc: Optional[int] = None):
    """Fully-integer direct depthwise conv: x_codes (B, H, W, C) int8
    activation codes, int32 window MACs, the producer's scalar power-of-two
    ``x_scale`` folded into the per-channel weight scale, and ``out_code=True``
    emitting the consumer's int8 codes from the fused epilogue.

    Zero-padding the code plane IS zero-padding the activation: fixed-point
    activation quant has no zero point, so code 0 decodes to 0.0 exactly."""
    B, H, W, C = x_codes.shape
    k2 = kh * kw
    xs = jnp.asarray(x_scale, jnp.float32)
    assert xs.ndim == 0 or xs.size == 1, \
        "depthwise int8-act path takes a scalar (per-tensor) activation scale"
    interp = resolve_interpret(interpret)
    if use_kernel is None:
        use_kernel = not interp
    if not use_kernel:
        c = unpack_rows(codes, bits)[:k2] if packed else codes
        return qconv_dw_int8_act_ref(x_codes, xs, c, scale, bias, kh=kh,
                                     kw=kw, strides=strides, pads=pads,
                                     bits=bits, relu=relu, act_qt=act_qt,
                                     out_code=out_code, out_dtype=out_dtype)
    sh, sw = strides
    oh, ow, hpad, wpad = out_spatial(H, W, kh, kw, strides, pads)
    x2, Hp, wpp, cp, owp = _prep_spatial(x_codes, hpad, wpad, kw, sw, ow)
    w, sp, bp, w_rows = _prep_weights(codes, scale, bias, k2, cp, bits, packed)
    # scalar activation scale folds into the channel scale — a power of two,
    # so the fold is bit-exact vs the oracle's grouping
    sp = sp * xs.reshape(())
    if bc is None:
        bc = pick_blocks_dw(B, Hp, wpp, cp, kh=kh, kw=kw, sh=sh, sw=sw,
                            oh=oh, ow=owp, w_rows=w_rows, bits=bits,
                            interpret=interp, int8_act=True, packed=packed)
    call = build_dw_call(B, Hp, wpp, cp, kh=kh, kw=kw, sh=sh, sw=sw, oh=oh,
                         ow=owp, w_rows=w_rows, bits=bits, int8_act=True,
                         bc=bc, out_dtype=out_dtype, interpret=interp,
                         has_bias=bias is not None, relu=relu, act_qt=act_qt,
                         packed=packed, emit_code=out_code)
    args = [x2] * kh + [w, sp] + ([bp] if bp is not None else [])
    y = call(*args)
    return y.reshape(B, oh, owp, cp)[:, :, :ow, :C]
