"""Operations and bytes, counted by the benchmark.

Two counts, for two different questions:

* :func:`useful_ops_per_image` — the int8 multiply-accumulates a model needs
  per image (two operations each), from the configuration's widths alone,
  unpadded: the numerator of ``mfu_int8``.  Max-pool, batch-norm and ReLU
  are not counted.
* :func:`event_call` — the operations and least bytes of one Pallas kernel
  call, from that call's operand and result shapes in the served program's
  HLO (the device trace names each operation by its HLO instruction), and
  the kernel's name from the lowered program (:func:`lowered_kernels`), by
  the kernel's own count file, ``bench/kernels/<kernel>.py`` (:mod:`hooks`).
  They follow whatever padding and tiling the program chose, so a kernel's
  roofline share says how close the kernel came to the chip's limits on the
  work it was given.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import hooks
import reference


def useful_ops_per_image(cfg: dict) -> int:
    """2 x the multiply-accumulates of every conv and FC layer of ``cfg``
    for one image, from its widths alone (its model module's count)."""
    return reference.model(cfg).useful_ops_per_image(cfg)


# bytes per element of the element types a kernel call can carry, under
# their StableHLO (lowered text) and HLO (compiled program, trace) names
_ELEM_BYTES = {"i8": 1, "ui8": 1, "s8": 1, "u8": 1, "i16": 2, "s16": 2,
               "bf16": 2, "f16": 2, "i32": 4, "ui32": 4, "s32": 4, "u32": 4,
               "f32": 4}
_STABLEHLO = re.compile(r"tensor<((?:[0-9]+x)*)([a-z]+[0-9]+)>")
_HLO = re.compile(r"\b([a-z]+[0-9]+)\[([0-9,]*)\]")
_KERNEL_NAME = re.compile(r'kernel_name = "([^"]+)"')

Shape = Tuple[Tuple[int, ...], int]     # (dims, bytes per element)


@dataclass(frozen=True)
class KernelCall:
    """One Pallas kernel call: its name, operations and least bytes (each
    operand buffer read once, the result written once)."""
    kernel: str
    ops: int
    bytes: int


def _stablehlo_shapes(text: str) -> List[Shape]:
    return [(tuple(int(d) for d in dims.split("x") if d), _ELEM_BYTES[t])
            for dims, t in _STABLEHLO.findall(text)]


def _hlo_shapes(text: str) -> List[Shape]:
    return [(tuple(int(d) for d in dims.split(",") if d), _ELEM_BYTES[t])
            for t, dims in _HLO.findall(text)]


def count(kernel: str, operands: Sequence[Shape],
          result: Sequence[Shape]) -> Optional[KernelCall]:
    """Operations and least bytes of one call of ``kernel`` from its shapes,
    by ``bench/kernels/<kernel>.py``'s ``count``; None for a kernel with no
    such file, which is then not counted."""
    mod = hooks.module("kernels", kernel)
    if mod is None:
        return None
    ops, nbytes = mod.count(operands, result)
    return KernelCall(kernel, ops, nbytes)


def signature(operands: Sequence[Shape], result: Sequence[Shape]) -> tuple:
    return tuple(operands), tuple(result)


def lowered_kernels(hlo_text: str) -> Dict[tuple, str]:
    """``{call signature: kernel name}`` of every call of a Pallas kernel
    that has a count file (``bench/kernels/<kernel>.py``) in the StableHLO
    text of a lowered program (``jax.jit(f).lower(x).as_text()``), where
    each call names its kernel."""
    out = {}
    for line in hlo_text.splitlines():
        name = _KERNEL_NAME.search(line)
        if ("tpu_custom_call" not in line or name is None
                or hooks.path("kernels", name.group(1)) is None):
            continue
        args, res = line[line.rindex(": (") + 3:].split(") -> ", 1)
        out[signature(_stablehlo_shapes(args),
                      _stablehlo_shapes(res))] = name.group(1)
    return out


def event_shapes(event_name: str) -> Optional[tuple]:
    """``(operands, result)`` shapes of a device-trace operation that is a
    Pallas call, else None.  The operation's name is its HLO instruction:
    ``%x = s8[M,N]{...} custom-call(s8[M,K]{...} %a, ...),
    custom_call_target="tpu_custom_call", ...``."""
    if 'custom_call_target="tpu_custom_call"' not in event_name:
        return None
    head, _, rest = event_name.partition(" custom-call(")
    return (_hlo_shapes(rest.partition("), custom_call_target")[0]),
            _hlo_shapes(head.partition(" = ")[2]))


def event_call(event_name: str, kernels_by_sig: Dict[tuple, str]
               ) -> Optional[KernelCall]:
    """The kernel call a device-trace operation is, or None: its shapes
    (:func:`event_shapes`) looked up among the lowered program's calls."""
    shapes = event_shapes(event_name)
    if shapes is None:
        return None
    kernel = kernels_by_sig.get(signature(*shapes))
    return None if kernel is None else count(kernel, *shapes)


def least_seconds(call: KernelCall, peaks: dict) -> float:
    """The least time the chip could take for ``call``: the larger of its
    operations over the int8 peak and its bytes over the memory bandwidth."""
    return max(call.ops / peaks["int8_ops_per_s"],
               call.bytes / peaks["hbm_bytes_per_s"])
