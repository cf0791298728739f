"""Pallas TPU kernel: direct depthwise conv in the integer code domain.

No im2col materialization: the ``(N*OH*OW, KH*KW*C)`` patch tensor the legacy
conv lowering builds is never formed.  Instead each grid invocation computes
ONE output row of one (batch, channel-block) slice straight from ``kh``
overlapping *input-row views* of the same padded activation array — block
height 1 makes the element row equal the block index, so the BlockSpec index
map ``(b*Hp + oh*sh + j, 0, c)`` expresses the sliding window without any
data duplication (the same multiple-views-of-one-array trick the qmatmul
kernel uses for its split-row packed activation chunks).  The column
stride needs no gather either: at ``sw > 1`` the wrapper orders each row's
columns by phase, so every tap is one contiguous slice (:func:`phase_offset`).

Depthwise structure makes the reduction tiny (``kh*kw`` taps per channel) and
purely channel-parallel, so the MAC loop is a VPU multiply-accumulate over
``(1, OW, bc)`` tiles — int32 on the fully-integer path — with the weight tap
matrix resident in VMEM: int8 master codes truncated to the active ``bits``
view in-VMEM, or the split-row sub-byte packed W4/W2 buffer
(:func:`repro.quant.pack.pack_rows` at the small depthwise alignment)
unpacked in-VMEM.  The fused bias + ReLU + (re)quant epilogue is shared with
qmatmul's oracle, so the exactness contract has ONE home
(:mod:`repro.kernels.qconv_dw.ref` accumulates in this kernel's exact order:
bit-exact on the fully-integer path, ulp-of-max on the float path).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# shared contract homes: nested truncation + sub-byte unpack (qmatmul) and
# the epilogue bodies (pure jnp, trace fine inside a Pallas kernel)
from repro.kernels.qmatmul.kernel import _truncate, _unpack_fields
from repro.kernels.qmatmul.ref import ActQt, epilogue_code_ref, epilogue_ref

# channel-block default: one lane tile
DEFAULT_BC = 128


def phase_offset(dx: int, sw: int, Wpp: int) -> int:
    """Start of tap ``dx``'s columns in a row whose ``Wpp`` columns are
    ordered by phase (``ops._prep_spatial``): input column ``dx + sw*o`` sits
    at ``phase_offset(dx, sw, Wpp) + o``.  At ``sw == 1`` the order is the
    identity and the offset is ``dx``."""
    return (dx % sw) * (Wpp // sw) + dx // sw


def qconv_dw_kernel(*refs, kh: int, kw: int, sw: int, ow: int, bits: int,
                    has_bias: bool, relu: bool, act_qt: Optional[ActQt],
                    int8_act: bool, pack_ratio: int):
    """One grid invocation = one (batch, output-row, channel-block) tile.

    Ref layout (in order):

    ``row_0 .. row_{kh-1}`` — the kh input rows of this output row's window:
    (1, Wpp, bc) views of the SAME padded activation array (int8 codes on the
    integer path, f32 on the float path), columns ordered by phase at
    ``sw > 1`` so each tap is a unit-stride slice (:func:`phase_offset`);
    ``w``  — weight taps: int8 codes (KRp, bc) or split-row sub-byte packed
    uint8 (Kp2/r, bc); rows beyond ``kh*kw`` are alignment padding;
    ``s``  — per-channel scale (1, bc), activation scale and sub-byte step
    pre-folded in;
    ``[b]`` — bias (1, bc), only ``has_bias``;
    ``o``  — output tile (1, OWp, bc); int8 codes when the epilogue emits
    codes, else the float dtype.
    """
    rows = refs[:kh]
    idx = kh
    w_ref, s_ref = refs[idx], refs[idx + 1]
    idx += 2
    b_ref = None
    if has_bias:
        b_ref = refs[idx]
        idx += 1
    o_ref = refs[idx]
    bc = o_ref.shape[-1]

    if pack_ratio > 1:
        fields = _unpack_fields(w_ref[...].astype(jnp.int32), bits, pack_ratio)
        wmat = jnp.concatenate(fields, axis=0)          # (Kp2, bc) q fields
        if not int8_act:
            wmat = wmat.astype(jnp.float32)
    else:
        wmat = _truncate(w_ref[...].astype(jnp.float32), bits)
        if int8_act:
            wmat = wmat.astype(jnp.int32)

    acc_dtype = jnp.int32 if int8_act else jnp.float32
    acc = jnp.zeros((1, ow, bc), acc_dtype)
    for j in range(kh):
        row = rows[j][...].astype(acc_dtype)            # (1, Wpp, bc)
        for dx in range(kw):
            start = phase_offset(dx, sw, row.shape[1])
            taps = jax.lax.slice_in_dim(row, start, start + ow, axis=1)
            acc = acc + taps * wmat[j * kw + dx][None, None, :]

    y = acc.astype(jnp.float32) * s_ref[...][:, None, :].astype(jnp.float32)
    if b_ref is not None:
        y = y + b_ref[...][:, None, :].astype(jnp.float32)
    if jnp.issubdtype(o_ref.dtype, jnp.integer):
        o_ref[...] = epilogue_code_ref(y, relu, act_qt).astype(o_ref.dtype)
    else:
        o_ref[...] = epilogue_ref(y, relu, act_qt).astype(o_ref.dtype)


def build_dw_call(B: int, Hp: int, Wpp: int, Cp: int, *, kh: int, kw: int,
                  sh: int, sw: int, oh: int, ow: int, w_rows: int, bits: int,
                  int8_act: bool, bc: int = DEFAULT_BC,
                  out_dtype=jnp.float32, interpret: bool = False,
                  has_bias: bool = False, relu: bool = False,
                  act_qt: Optional[ActQt] = None, packed: bool = False,
                  emit_code: bool = False):
    """A ``pallas_call`` over a padded depthwise problem.

    Operands: activations reshaped to (B*Hp, Wpp, Cp), each row's columns
    ordered by phase when ``sw > 1``, with ``Wpp`` a multiple of ``sw`` and
    ``(kw-1)//sw + ow <= Wpp//sw`` (so every tap slice stays in its phase),
    weights (w_rows, Cp) — codes padded to ``w_rows >= kh*kw`` rows, or the
    packed buffer with ``w_rows = Kp2 / (8//bits)`` byte rows — scale (1, Cp)
    and optional bias (1, Cp).  Output: (B*oh, ow, Cp)."""
    if emit_code:
        assert act_qt is not None, "emit_code needs the output act_qt"
        assert act_qt[1] >= -128 and act_qt[2] <= 127, \
            f"act_qt {act_qt} does not fit int8 codes"
    if packed:
        assert bits in (4, 2), f"sub-byte packing needs bits in (4, 2): {bits}"
    assert Cp % bc == 0, (Cp, bc)
    assert Wpp % sw == 0 and (kw - 1) // sw + ow <= Wpp // sw, \
        (Wpp, kw, sw, ow)
    grid = (B, oh, Cp // bc)

    kern = functools.partial(
        qconv_dw_kernel, kh=kh, kw=kw, sw=sw, ow=ow, bits=bits,
        has_bias=has_bias, relu=relu, act_qt=act_qt, int8_act=int8_act,
        pack_ratio=(8 // bits) if packed else 1)
    # kh views of the one padded activation array: view j's block row is the
    # input row feeding tap row j of output row oh (block height 1 => block
    # index == element row)
    in_specs = [
        pl.BlockSpec((1, Wpp, bc),
                     functools.partial(
                         lambda b, o, c, j: (b * Hp + o * sh + j, 0, c), j=j))
        for j in range(kh)
    ]
    in_specs.append(pl.BlockSpec((w_rows, bc), lambda b, o, c: (0, c)))
    in_specs.append(pl.BlockSpec((1, bc), lambda b, o, c: (0, c)))
    if has_bias:
        in_specs.append(pl.BlockSpec((1, bc), lambda b, o, c: (0, c)))

    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, ow, bc), lambda b, o, c: (b * oh + o, 0, c)),
        out_shape=jax.ShapeDtypeStruct((B * oh, ow, Cp),
                                       jnp.int8 if emit_code else out_dtype),
        interpret=interpret,
    )
