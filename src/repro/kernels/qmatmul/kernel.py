"""Pallas TPU kernel: dequant-fused quantized matmul with a fused epilogue.

Weights live in HBM as int8 master codes (one copy serves every working point,
DESIGN.md §2 MDC row); each (bk, bn) tile is streamed into VMEM, truncated to
the active ``bits`` view, dequantized with the per-channel scale and fed to the
MXU against a (bm, bk) activation tile.  f32 accumulation in a VMEM scratch
tile across the k grid dim (TPU grid is sequential => scratch carries).

Three orthogonal extensions make this the *fully-integer* engine:

* ``int8_act`` — activations arrive as int8 codes (the producer FIFO's
  fixed-point integers); MACs run on the MXU int8 path with
  ``preferred_element_type=int32`` and the per-tensor activation scale is
  pre-folded into the per-channel weight scale (a power of two — exact).
* ``pack_ratio`` — the weight tile is *sub-byte packed* (split-row layout,
  :func:`repro.quant.pack.pack_rows`): a (bk/r, bn) uint8 tile is DMA'd from
  HBM and unpacked in-VMEM into ``r`` code tiles, each MAC'd against its own
  (bm, bk/r) activation tile (the r activation views index disjoint K chunks
  of the SAME array — no data duplication, just r BlockSpecs).  HBM traffic
  for the weight stream drops to bits/8 of the W8 view.
* ``emit_code`` — the epilogue (per-channel rescale, optional bias, ReLU and
  fixed-point activation quant, bit-identical to ``fixedpoint.fake_quant``)
  stores the int8 *code* instead of the dequantized value, so codes — not
  floats — flow through the inter-layer FIFO to the next kernel.

Block shapes are MXU-aligned: multiples of 128 on M/N, and on K a multiple
of 128 lanes per activation view (``128 * r`` on the packed path), which the
TPU compiler requires of every block's last dimension.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the epilogue body is shared with the jnp oracle (pure jnp, traces fine
# inside a Pallas kernel) so the bit-exactness contract has ONE home
from repro.kernels.qmatmul.ref import ActQt, epilogue_code_ref, epilogue_ref

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 512


def _truncate(codes_f32, bits: int):
    """Nested ``bits``-bit view of int8 codes (matches quant.ptq.derive_view)."""
    if bits >= 8:
        return codes_f32
    step = float(1 << (8 - bits))
    q = jnp.clip(jnp.round(codes_f32 / step), -(2 ** (bits - 1)),
                 2 ** (bits - 1) - 1)
    return q * step


def _unpack_fields(packed_i32, bits: int, pack_ratio: int):
    """Split-row packed uint8 tile -> ``pack_ratio`` integer code tiles
    (the ``q`` fields; the 2^(8-bits) step is pre-folded into the scale)."""
    half, mask = 1 << (bits - 1), (1 << bits) - 1
    outs = []
    for j in range(pack_ratio):
        f = (packed_i32 >> (j * bits)) & mask
        outs.append(jnp.where(f >= half, f - (1 << bits), f))
    return outs


def qgemm_kernel(*refs, bits: int, nk: int, has_bias: bool, relu: bool,
                 act_qt: Optional[ActQt], int8_act: bool = False,
                 pack_ratio: int = 1, has_xscale: bool = False):
    """Grid (m, n, k).  Ref layout (in order):

    ``x_0 .. x_{r-1}`` — activation tiles (bm, bk/r); bf16 float path or int8
    code path; r = ``pack_ratio`` views of the SAME array over disjoint K
    chunks (r == 1 when the weight tile is unpacked);
    ``[xs]``          — per-row activation scale (bm, 1), only ``has_xscale``
    (the legacy per-row integer path; the writer path folds its per-tensor
    power-of-two scale into ``s`` instead);
    ``w``             — weight tile: int8 codes (bk, bn) or split-row packed
    uint8 (bk/r, bn);
    ``s``             — per-channel scale (1, bn) with the activation scale
    and the sub-byte step pre-folded in;
    ``[b]``           — bias (1, bn), only ``has_bias``;
    ``o``             — output tile (bm, bn); int8 codes when the epilogue
    emits codes, else the float dtype;
    ``acc``           — VMEM scratch (bm, bn), int32 on the integer path.
    """
    r = pack_ratio
    xs = list(refs[:r])
    idx = r
    xs_ref = None
    if has_xscale:
        xs_ref = refs[idx]
        idx += 1
    w_ref, s_ref = refs[idx], refs[idx + 1]
    idx += 2
    b_ref = None
    if has_bias:
        b_ref = refs[idx]
        idx += 1
    o_ref, acc_ref = refs[idx], refs[idx + 1]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # the integer path keeps both MXU operands int8 (int32 accumulation):
    # the W8 codes, the truncated W4/W2 views (multiples of the step inside
    # [-128, 112]) and the unpacked q fields all fit int8 exactly
    if r == 1:
        if int8_act:
            w = w_ref[...]
            if bits < 8:
                # same round-half-even rule as ptq.derive_view (bit-exact)
                w = _truncate(w.astype(jnp.float32), bits).astype(jnp.int8)
            acc_ref[...] += jax.lax.dot(xs[0][...], w,
                                        preferred_element_type=jnp.int32)
        else:
            w = _truncate(w_ref[...].astype(jnp.float32), bits)
            acc_ref[...] += jax.lax.dot(xs[0][...].astype(jnp.float32), w,
                                        preferred_element_type=jnp.float32)
    else:
        fields = _unpack_fields(w_ref[...].astype(jnp.int32), bits, r)
        if int8_act:
            for x_ref, q in zip(xs, fields):
                acc_ref[...] += jax.lax.dot(
                    x_ref[...], q.astype(jnp.int8),
                    preferred_element_type=jnp.int32)
        else:
            for x_ref, q in zip(xs, fields):
                acc_ref[...] += jax.lax.dot(
                    x_ref[...].astype(jnp.float32), q.astype(jnp.float32),
                    preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        y = acc_ref[...].astype(jnp.float32)
        if xs_ref is not None:
            y = y * xs_ref[...].astype(jnp.float32)
        y = y * s_ref[...].astype(jnp.float32)
        if b_ref is not None:
            y = y + b_ref[...].astype(jnp.float32)
        if jnp.issubdtype(o_ref.dtype, jnp.integer):
            o_ref[...] = epilogue_code_ref(y, relu, act_qt).astype(o_ref.dtype)
        else:
            o_ref[...] = epilogue_ref(y, relu, act_qt).astype(o_ref.dtype)


def build_call(M: int, K: int, N: int, *, bits: int, int8_act: bool,
               bm: int = DEFAULT_BM, bn: int = DEFAULT_BN, bk: int = DEFAULT_BK,
               out_dtype=jnp.bfloat16, interpret: bool = False,
               has_bias: bool = False, relu: bool = False,
               act_qt: Optional[ActQt] = None, packed: bool = False,
               emit_code: bool = False, has_xscale: bool = False):
    """A ``pallas_call`` for a (padded) M×K×N problem.

    ``K`` is the *logical* reduction dim; with ``packed=True`` the weight
    operand is the split-row packed uint8 buffer of shape (K/r, N) with
    ``r = 8 // bits`` (see :func:`repro.quant.pack.pack_rows`) and the
    activation operand is passed ``r`` times with BlockSpecs covering its r
    contiguous K chunks.  ``emit_code=True`` stores int8 codes (``act_qt``
    required)."""
    r = (8 // bits) if packed else 1
    if packed:
        assert bits in (4, 2), f"sub-byte packing needs bits in (4, 2): {bits}"
    if emit_code:
        assert act_qt is not None, "emit_code needs the output act_qt"
        assert act_qt[1] >= -128 and act_qt[2] <= 127, \
            f"act_qt {act_qt} does not fit int8 codes"
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, K, N, bm, bn, bk)
    # each activation view's block is (bm, bk/r): whole 128-lane tiles
    assert bk % (128 * r) == 0, f"bk={bk} is not a multiple of 128*{r}"
    nk = K // bk
    grid = (M // bm, N // bn, nk)

    kern = functools.partial(qgemm_kernel, bits=bits, nk=nk, has_bias=has_bias,
                             relu=relu, act_qt=act_qt, int8_act=int8_act,
                             pack_ratio=r, has_xscale=has_xscale)
    # r activation views over disjoint K chunks of the same array: view j's
    # block-column c covers x columns [(j*nk + c) * bk/r, ...) — chunk j of
    # the split-row layout
    in_specs = [
        pl.BlockSpec((bm, bk // r),
                     functools.partial(lambda m, n, k, j: (m, j * nk + k), j=j))
        for j in range(r)
    ]
    if has_xscale:
        in_specs.append(pl.BlockSpec((bm, 1), lambda m, n, k: (m, 0)))
    in_specs.append(pl.BlockSpec((bk // r, bn), lambda m, n, k: (k, n)))
    in_specs.append(pl.BlockSpec((1, bn), lambda m, n, k: (0, n)))
    if has_bias:
        in_specs.append(pl.BlockSpec((1, bn), lambda m, n, k: (0, n)))
    acc_dtype = jnp.int32 if int8_act else jnp.float32

    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N),
                                       jnp.int8 if emit_code else out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        interpret=interpret,
    )
