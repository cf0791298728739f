"""jit'd wrapper: SAME-padded stride-1 conv through the line-buffer kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.conv2d_stream.kernel import build_call
from repro.kernels.conv2d_stream.ref import conv2d_ref
from repro.kernels.qmatmul.ops import resolve_interpret


@functools.partial(jax.jit, static_argnames=("interpret", "use_kernel"))
def conv2d_stream(x, w, b, *, interpret: Optional[bool] = None,
                  use_kernel: bool = True):
    """x: (B, H, W, Cin); w: (kh, kw, Cin, Cout); b: (Cout,) — SAME, stride 1.
    ``interpret=None`` compiles the kernel on TPU and interprets it elsewhere
    (:func:`~repro.kernels.qmatmul.ops.resolve_interpret`)."""
    if not use_kernel:
        return conv2d_ref(x, w, b)
    B, H, W, Cin = x.shape
    kh, kw, _, Cout = w.shape
    ph, pw = kh // 2, kw // 2
    xp = jnp.pad(x, ((0, 0), (ph, kh - 1 - ph), (pw, kw - 1 - pw), (0, 0)))
    call = build_call(B, H, W, Cin, Cout, kh, kw, out_dtype=x.dtype,
                      interpret=resolve_interpret(interpret))
    return call(xp, w, b.reshape(1, -1))
