"""Packed weight storage: the one-copy-many-points artifact.

Three layers live here:

* :class:`PackedWeights` / :class:`PackedTensor` — every >=2-D initializer of
  a graph quantized ONCE to int8 master codes + per-output-channel f32 scales.
  W4/W2 working points are *nested truncations* of the same codes
  (``quant.ptq.derive_view``), so N working points share ONE buffer — the
  paper's MDC weight sharing, and what lets ``AccelServer`` switch precision
  per batch with zero weight movement.  The dequant-fused
  ``repro.kernels.qmatmul`` kernels stream these codes directly.
* sub-byte **HBM residency**: ``PackedTensor.packed_view(bits)`` stores the
  W4/W2 views nibble/crumb-packed into ``uint8`` with the *split-row* layout
  (:func:`pack_rows`), cutting the resident weight buffer to ~1/2 and ~1/4 of
  the W8 codes — the paper's BRAM-column effect realized as real HBM
  bandwidth: the qmatmul kernels unpack each k-block in-VMEM.
* generic bit-packing helpers (int4: 2/byte, int2: 4/byte) along the last
  dim (``pack_int4`` / ``pack_int2``) — layout-agnostic round-trip utilities.

Split-row layout
----------------
``pack_rows(codes, bits)`` pads K (the reduction dim) up to ``pack_align(bits)``,
splits the rows into ``r = 8 // bits`` contiguous chunks of ``Kp / r`` rows,
and packs row ``i`` of every chunk into one byte (chunk ``j`` occupies bit
field ``j*bits``).  A contiguous *byte-row* block of the packed buffer then
maps to ``r`` contiguous *code-row* blocks of the logical matrix — exactly
what a Pallas kernel wants: it streams one packed (bk/r, bn) tile plus the
``r`` matching activation tiles and never reshuffles lanes in VMEM.  The
stored field is ``q = view / 2^(8-bits)`` (the true ``bits``-bit integer), so
kernels fold the power-of-two step into the channel scale instead of
multiplying it back per element.
"""
from __future__ import annotations

import threading
import zlib
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

# the qmatmul kernels' lane tile: K of an unpacked (W8) weight is padded to it
PACK_ALIGN = 128

# working points with a sub-byte packed representation
SUB_BYTE_BITS = (4, 2)


def pack_align(bits: int) -> int:
    """K alignment of a ``bits``-bit matmul weight: ``PACK_ALIGN`` per
    packed activation view, i.e. ``128 * (8 // bits)`` below W8.  The kernel
    streams a (bk/r)-row packed tile against r activation blocks of bk/r
    lanes each, and the TPU compiler needs every such block to span whole
    128-lane tiles — so a stored view padded this way streams without a
    repack."""
    return PACK_ALIGN * (8 // bits) if bits in SUB_BYTE_BITS else PACK_ALIGN


def _crc32(arr) -> int:
    """CRC32 of a buffer's raw bytes (the per-region integrity checksum)."""
    return zlib.crc32(np.ascontiguousarray(np.asarray(arr)).tobytes())


@dataclass(frozen=True)
class Region:
    """One independently-checksummed buffer of a :class:`PackedWeights`:
    a tensor's int8 master codes, its f32 per-channel scales, or one cached
    sub-byte packed view (identified by ``(bits, align)``).  The scrubber
    walks these; ``nbytes`` is what one verification of the region costs
    against its rate budget."""
    tensor: str
    kind: str                  # "codes" | "scale" | "view"
    bits: Optional[int] = None     # view regions only
    align: Optional[int] = None    # view regions only
    nbytes: int = 0

    def label(self) -> str:
        if self.kind == "view":
            return f"{self.tensor}:view(w{self.bits},align={self.align})"
        return f"{self.tensor}:{self.kind}"


@dataclass(frozen=True)
class RegionMismatch:
    """A failed region verification: the buffer's bytes no longer hash to
    the checksum sealed at pack time (a silent-data-corruption detection).
    ``repairable`` regions (the W4/W2 packed views — nested truncations of
    the master codes) can be re-derived bit-exactly; master-code or scale
    corruption has no redundant source and must escalate."""
    region: Region
    expected_crc: int
    actual_crc: int

    @property
    def repairable(self) -> bool:
        return self.region.kind == "view"

    def __str__(self) -> str:
        fix = "repairable from master" if self.repairable else "UNREPAIRABLE"
        return (f"checksum mismatch in {self.region.label()} "
                f"({self.region.nbytes} bytes, expected "
                f"{self.expected_crc:#010x}, got {self.actual_crc:#010x}; "
                f"{fix})")


def _pad_rows(codes, align: int):
    r = (-codes.shape[0]) % align
    if r == 0:
        return codes
    return jnp.pad(codes, ((0, r),) + ((0, 0),) * (codes.ndim - 1))


def pack_rows(codes, bits: int, align: Optional[int] = None):
    """int8 master codes (K, N) -> split-row packed uint8 (Kp/r, N).

    ``r = 8 // bits``; K is zero-padded to ``align`` (default
    :func:`pack_align`; code 0 packs to a zero
    field and contributes nothing to a MAC).  Byte ``i`` holds the ``bits``-bit
    integer ``q`` of rows ``i + j*(Kp/r)`` for ``j = 0..r-1``, field ``j`` at
    bit ``j*bits``.  ``q`` is the rounded nested truncation — identical to
    ``derive_view(codes, bits) / 2^(8-bits)``."""
    assert bits in SUB_BYTE_BITS, f"no sub-byte packing for bits={bits}"
    r = 8 // bits
    shift = 8 - bits
    step = 1 << shift
    half = 1 << (bits - 1)
    cp = _pad_rows(jnp.asarray(codes), align or pack_align(bits))
    kp = cp.shape[0]
    q = jnp.clip(jnp.round(cp.astype(jnp.float32) / step),
                 -half, half - 1).astype(jnp.int32)
    chunks = q.reshape(r, kp // r, *cp.shape[1:])
    mask = (1 << bits) - 1
    out = jnp.zeros(chunks.shape[1:], jnp.int32)
    for j in range(r):
        out = out | ((chunks[j] & mask) << (j * bits))
    return out.astype(jnp.uint8)


def unpack_rows(packed, bits: int):
    """Split-row packed uint8 (Kp/r, N) -> int8 codes (Kp, N) in the *view*
    domain (``q * 2^(8-bits)``, i.e. exactly ``derive_view`` of the master)."""
    assert bits in SUB_BYTE_BITS, f"no sub-byte packing for bits={bits}"
    r = 8 // bits
    step = 1 << (8 - bits)
    half = 1 << (bits - 1)
    mask = (1 << bits) - 1
    p = packed.astype(jnp.int32)
    chunks = []
    for j in range(r):
        f = (p >> (j * bits)) & mask
        q = jnp.where(f >= half, f - (1 << bits), f)
        chunks.append(q * step)
    return jnp.concatenate(chunks, axis=0).astype(jnp.int8)


# ---------------------------------------------------------------------------
# Packed master-code artifact (graph-level analogue of ptq.QuantizedParams)
# ---------------------------------------------------------------------------

@dataclass
class PackedTensor:
    """One weight, quantized once: int8 master codes + per-out-channel scale.

    ``codes`` keeps the original weight shape (HWIO for conv, (K, N) for
    Gemm); ``scale`` is f32 and broadcastable against it (keepdims over the
    last axis).  Low-bit working points are derived views of the same codes —
    no storage per point; the W4/W2 views additionally cache a *sub-byte
    packed* buffer (:meth:`packed_view`) so their HBM residency really is
    bits/8 of the master's.

    Every region (master codes, scales, each cached packed view) is sealed
    with a CRC32 at creation; :meth:`verify` re-hashes the live buffers and
    reports typed :class:`RegionMismatch` entries for any silent bit flip.
    Corrupted views are re-derivable from the intact master
    (:meth:`repair_view` — nested truncation makes repair free); the cache
    and checksum dicts are lock-guarded because the fleet heal path rebuilds
    replicas while siblings serve from the same tensors."""

    codes: jax.Array     # int8, original weight shape
    scale: jax.Array     # f32, per-output-channel (last dim), keepdims
    # cache key: (bits, K-alignment) — one resident buffer per view
    _packed: Dict[tuple, jax.Array] = field(default_factory=dict, repr=False,
                                            compare=False)
    # sealed checksums: "codes" / "scale" / ("view", bits, align) -> CRC32
    _crc: Dict[object, int] = field(default_factory=dict, repr=False,
                                    compare=False)
    # guards first-touch view derivation AND checksum (re)sealing
    _lock: threading.Lock = field(default_factory=threading.Lock, repr=False,
                                  compare=False)

    def __post_init__(self):
        self.seal()

    def seal(self) -> None:
        """(Re)seal the master-code and scale checksums from the CURRENT
        buffers (called at pack time)."""
        with self._lock:
            self._crc["codes"] = _crc32(self.codes)
            self._crc["scale"] = _crc32(self.scale)

    def view(self, bits: int) -> jax.Array:
        """The ``bits``-bit nested-truncation view of the master codes."""
        from repro.quant.ptq import derive_view
        return derive_view(self.codes, bits)

    def dequant(self, bits: int = 8, dtype=jnp.float32) -> jax.Array:
        """Fake-quant float copy at a working point (the legacy writer path —
        under jit over constant codes XLA folds this away)."""
        from repro.quant.ptq import dequant
        return dequant(self.codes, self.scale, bits, dtype)

    def codes_2d(self) -> jax.Array:
        """Codes flattened to (K, N) for the qmatmul kernels (N = out chans)."""
        return self.codes.reshape(-1, self.codes.shape[-1])

    def scale_1d(self) -> jax.Array:
        return self.scale.reshape(-1)

    def packed_view(self, bits: int, align: Optional[int] = None) -> jax.Array:
        """Split-row sub-byte packed W4/W2 buffer (cached; K padded to
        ``align`` so kernels stream it without a repack).  The default
        alignment is the qmatmul kernels' :func:`pack_align`; the
        depthwise-direct kernels pass
        a small alignment so a 3x3 window (K = 9) is not padded 14x."""
        if bits not in SUB_BYTE_BITS:
            raise ValueError(f"packed_view is for bits in {SUB_BYTE_BITS}, "
                             f"got {bits} (the W8 view IS the master codes)")
        key = (bits, int(align or pack_align(bits)))
        # first-touch derivation is lock-guarded: the fleet heal path builds
        # a fresh replica's executables while sibling pumps serve from the
        # same PackedWeights, so two threads may race the cache miss
        with self._lock:
            buf = self._packed.get(key)
            if buf is None:
                # first touch usually happens while a served executable is
                # traced: derive the buffer eagerly from the concrete master
                # codes so the cache holds (and the CRC seals) real bytes
                with jax.ensure_compile_time_eval():
                    buf = pack_rows(self.codes_2d(), bits, align=key[1])
                self._packed[key] = buf
                self._crc[("view", *key)] = _crc32(buf)
        return buf

    # -- integrity -----------------------------------------------------------
    def regions(self, name: str, bits: Optional[int] = None) -> List[Region]:
        """The checksummed regions of this tensor, filtered by working
        point: ``None`` = every region; ``8`` = master codes + scales;
        ``4``/``2`` = that point's cached packed views + the scales (what
        the sub-byte serving path actually reads)."""
        regs: List[Region] = []
        with self._lock:
            view_keys = list(self._packed)
        if bits is None or bits == 8:
            regs.append(Region(name, "codes", nbytes=int(self.codes.size)))
        regs.append(Region(name, "scale", nbytes=4 * int(self.scale.size)))
        for (b, align) in view_keys:
            if bits is None or b == bits:
                with self._lock:
                    nb = int(self._packed[(b, align)].size)
                regs.append(Region(name, "view", bits=b, align=align,
                                   nbytes=nb))
        return regs

    def _buffer(self, region: Region):
        if region.kind == "codes":
            return self.codes
        if region.kind == "scale":
            return self.scale
        with self._lock:
            return self._packed.get((region.bits, region.align))

    def _sealed_crc(self, region: Region) -> Optional[int]:
        key = (region.kind if region.kind != "view"
               else ("view", region.bits, region.align))
        with self._lock:
            return self._crc.get(key)

    def verify_region(self, region: Region) -> Optional[RegionMismatch]:
        """Re-hash one region against its sealed checksum; ``None`` = clean.
        An evicted/never-derived view region verifies clean (nothing to
        corrupt)."""
        buf = self._buffer(region)
        expected = self._sealed_crc(region)
        if buf is None or expected is None:
            return None
        actual = _crc32(buf)
        if actual == expected:
            return None
        return RegionMismatch(region, expected, actual)

    def verify(self, name: str, bits: Optional[int] = None
               ) -> List[RegionMismatch]:
        return [m for m in (self.verify_region(r)
                            for r in self.regions(name, bits))
                if m is not None]

    def repair_view(self, bits: int, align: Optional[int] = None) -> jax.Array:
        """Re-derive one packed view bit-exactly from the master codes and
        reseal its checksum — the self-healing half of SDC handling (views
        are nested truncations, so repair costs one re-pack, no reload).
        The caller must have verified the master codes first: repairing from
        a corrupted master would launder the corruption into a 'clean'
        checksum."""
        if bits not in SUB_BYTE_BITS:
            raise ValueError(f"only sub-byte views are repairable, got "
                             f"bits={bits}")
        key = (bits, int(align or pack_align(bits)))
        with self._lock:
            fresh = pack_rows(self.codes_2d(), bits, align=key[1])
            self._packed[key] = fresh
            self._crc[("view", *key)] = _crc32(fresh)
        return fresh

    @property
    def nbytes(self) -> int:
        """Master storage: 1 byte/code + 4 bytes/scale (shared by all points)."""
        return int(self.codes.size) + 4 * int(self.scale.size)

    def view_nbytes(self, bits: int, align: Optional[int] = None) -> int:
        """Resident HBM bytes of the ``bits``-bit view on the kernel path:
        the streamed weight buffer (K padded to ``align``, default
        :func:`pack_align`, sub-byte packed below W8) plus the f32 channel
        scales."""
        k, n = self.codes_2d().shape
        align = align or pack_align(bits)
        kp = k + ((-k) % align)
        if bits in SUB_BYTE_BITS:
            buf = (kp // (8 // bits)) * n
        else:
            buf = kp * n
        return buf + 4 * int(self.scale.size)


@dataclass
class PackedWeights:
    """All of a graph's quantizable initializers packed to shared master codes.

    ``tensors`` holds the packed >=2-D weights; ``passthrough`` everything that
    stays float (biases, norm stats, 1-D tensors).  One instance backs every
    working-point executable of a :class:`~repro.core.writers.qjax_writer.
    QJaxWriter` — switching W8 -> W4 -> W2 re-reads the same buffers (W8: the
    int8 master; W4/W2: its cached sub-byte packed views)."""

    tensors: Dict[str, PackedTensor]
    passthrough: Dict[str, jax.Array]

    @classmethod
    def from_initializers(cls, initializers: Dict) -> "PackedWeights":
        from repro.quant.ptq import is_quantizable, quantize_channelwise
        tensors, passthrough = {}, {}
        for name, arr in initializers.items():
            w = jnp.asarray(arr)
            if is_quantizable(name, w):
                tensors[name] = PackedTensor(*quantize_channelwise(w))
            else:
                passthrough[name] = w
        return cls(tensors, passthrough)

    def dequantized(self, bits: int = 8, dtype=jnp.float32) -> Dict[str, jax.Array]:
        """Fake-quant float copies at a working point (the pre-packed-engine
        baseline: what each per-point executable used to hold)."""
        out = dict(self.passthrough)
        for name, t in self.tensors.items():
            out[name] = t.dequant(bits, dtype)
        return out

    def code_bytes(self) -> int:
        """Bytes of the shared master buffer (codes + scales)."""
        return sum(t.nbytes for t in self.tensors.values())

    # -- integrity -----------------------------------------------------------
    def regions(self, bits: Optional[int] = None) -> List[Region]:
        """Every checksummed region across all tensors (see
        :meth:`PackedTensor.regions` for the ``bits`` filter) — the
        scrubber's round-robin walk list."""
        return [r for name, t in self.tensors.items()
                for r in t.regions(name, bits)]

    def verify_region(self, region: Region) -> Optional[RegionMismatch]:
        t = self.tensors.get(region.tensor)
        if t is None:
            return None
        return t.verify_region(region)

    def verify(self, bits: Optional[int] = None) -> List[RegionMismatch]:
        """Re-hash every region (or only the ``bits`` working point's
        regions) against the checksums sealed at pack time; returns the
        typed mismatches — ``[]`` means the buffer is clean.  One shared
        buffer backs every working point on every replica, so this is THE
        silent-data-corruption detector for the whole fleet."""
        return [m for name, t in self.tensors.items()
                for m in t.verify(name, bits)]

    def repair(self, mismatch: RegionMismatch) -> jax.Array:
        """Repair one *view* mismatch by re-deriving the packed buffer from
        the (intact) master codes; raises ``ValueError`` for master-code or
        scale corruption, which has no redundant source here — callers
        escalate those (replica ejection / rebuild from the original
        initializers)."""
        r = mismatch.region
        if not mismatch.repairable:
            raise ValueError(f"cannot repair {r.label()}: only derived "
                             "views re-derive from the master codes")
        return self.tensors[r.tensor].repair_view(r.bits, align=r.align)

    def view_bytes(self, bits: int,
                   caps: Optional[Dict[str, int]] = None) -> int:
        """Resident streamed weight bytes at a working point (sub-byte packed
        buffers below W8; see :meth:`PackedTensor.view_nbytes`).

        ``caps`` optionally bounds individual initializers below the runtime
        view (``{name: max_bits}`` — the per-layer precision caps a
        :class:`~repro.quant.qtypes.PrecisionMap` realizes through
        ``QJaxContext.weight_bits``): the effective bits of a capped tensor
        are ``min(bits, caps[name])``, exactly what the mixed-precision
        executable streams.  The DSE's weight-bytes budget term is this
        number."""
        caps = caps or {}
        return sum(t.view_nbytes(min(bits, caps.get(name, bits)))
                   for name, t in self.tensors.items())

    def view_bytes_bound(self, bits: int) -> int:
        """Upper bound of :meth:`view_bytes` at ``bits``: ``bits/8`` of the
        W8 view plus, per tensor, one ``PACK_ALIGN``-row tile and its scales.
        A packed view pads K to ``pack_align(bits) = 128 * 8/bits`` rows
        where the W8 view pads it to 128, so a tensor whose K is far below
        ``128 * 8/bits`` (a 3x3 conv over few channels) saves nothing."""
        slack = sum((PACK_ALIGN + 4) * int(t.codes_2d().shape[1])
                    for t in self.tensors.values())
        return (bits * self.view_bytes(8)) // 8 + slack

    def sharing_report(self, n_points: int = 3) -> Dict[str, float]:
        """Merged-vs-separate weight storage for ``n_points`` working points
        (the MDC LUT-sharing story, in bytes): the shared master vs each point
        holding its own int8 copy (a 1/n_points drop by construction), and —
        the empirical ``sharing_ratio`` — vs the legacy per-point fake-quant
        f32 copies the writers used to bake into each executable.  The
        ``view_bytes`` entry accounts the *streamed* buffer per point with
        sub-byte packing (what actually moves HBM -> VMEM at W4/W2)."""
        shared = self.code_bytes()
        n_elems = sum(int(t.codes.size) for t in self.tensors.values())
        f32_copies = n_points * 4 * n_elems
        return {
            "n_points": n_points,
            "shared_bytes": shared,
            "per_point_copy_bytes": n_points * shared,
            "per_point_f32_bytes": f32_copies,
            "sharing_ratio": f32_copies / max(shared, 1),
            "view_bytes": {b: self.view_bytes(b) for b in (8, *SUB_BYTE_BITS)},
        }


def pack_int4(codes):
    """codes: int8 array in [-8, 7], last dim even -> uint8 packed (…, n/2)."""
    assert codes.shape[-1] % 2 == 0
    u = (codes.astype(jnp.int32) & 0xF).astype(jnp.uint8)
    lo, hi = u[..., 0::2], u[..., 1::2]
    return lo | (hi << 4)


def unpack_int4(packed):
    """uint8 (…, n/2) -> int8 (…, n) in [-8, 7]."""
    lo = (packed & 0xF).astype(jnp.int8)
    hi = ((packed >> 4) & 0xF).astype(jnp.int8)
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = jnp.where(hi >= 8, hi - 16, hi)
    out = jnp.stack([lo, hi], axis=-1)
    return out.reshape(*packed.shape[:-1], packed.shape[-1] * 2)


def pack_int2(codes):
    """codes: int8 in [-2, 1], last dim % 4 == 0 -> uint8 packed (…, n/4)."""
    assert codes.shape[-1] % 4 == 0
    u = (codes.astype(jnp.int32) & 0x3).astype(jnp.uint8)
    b0, b1, b2, b3 = u[..., 0::4], u[..., 1::4], u[..., 2::4], u[..., 3::4]
    return b0 | (b1 << 2) | (b2 << 4) | (b3 << 6)


def unpack_int2(packed):
    outs = []
    for sh in (0, 2, 4, 6):
        v = ((packed >> sh) & 0x3).astype(jnp.int8)
        outs.append(jnp.where(v >= 2, v - 4, v))
    out = jnp.stack(outs, axis=-1)
    return out.reshape(*packed.shape[:-1], packed.shape[-1] * 4)
