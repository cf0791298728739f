"""The comparison that decides ``correct``.

A sample of the requests the window finished (drawn from the seed, the
longest request among them) is run through the plain references of
:mod:`reference`, under HIGHEST matmul precision and in fixed-size blocks of
rows, and the served logits are held against them:

* ``qref_err`` — against the model at the configuration's precision
  (:func:`reference.quantized_forward`: W8 weights, D8 activations on the
  grids the benchmark's own calibration sets), the relative error of all
  sampled logits together, ``|served - ref|_F / |ref|_F``.  A sound server
  computes this model; it differs only where a rounding lands on a grid
  point's edge differently, or a calibrated range sits at a power of two;
* ``logit_err`` — the same against the float32 model;
* ``worst_row_err`` — the worst row's relative L2 error against float32;
* ``top1_miss`` — the share of rows whose top-1 class is not float32's;
* ``top1_gap`` — the widest gap by which float32's logit of the class the
  server ranked first lies below its best logit, in units of that row's
  logit standard deviation.

A number is compared when the configuration file gives it a limit
(``limits``), set from the readings of sound runs and of the
lower-precision control (PERF.md); the others are readings only.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import numpy as np

import reference

NUMBERS = ("qref_err", "logit_err", "worst_row_err", "top1_miss", "top1_gap")
BLOCK_ROWS = 32


def _blocks(fn, xs: np.ndarray) -> np.ndarray:
    """``fn`` over ``xs``, ``BLOCK_ROWS`` rows at a time (the last block
    zero-padded, so one program serves every block)."""
    out = []
    with jax.default_matmul_precision("highest"):
        for i in range(0, len(xs), BLOCK_ROWS):
            blk = xs[i:i + BLOCK_ROWS]
            pad = np.zeros((BLOCK_ROWS - len(blk), *xs.shape[1:]), xs.dtype)
            out.append(np.asarray(fn(np.concatenate([blk, pad])))[:len(blk)])
    return np.concatenate(out)


def compare(served: np.ndarray, ref: np.ndarray,
            qref: np.ndarray) -> Dict[str, float]:
    """The numbers of served rows against their reference rows."""
    row_err = np.linalg.norm(served - ref, axis=1) / np.linalg.norm(ref, axis=1)
    rows = np.arange(len(ref))
    top = served.argmax(axis=1)
    gap = (ref.max(axis=1) - ref[rows, top]) / ref.std(axis=1)
    return {
        "qref_err": float(np.linalg.norm(served - qref)
                          / np.linalg.norm(qref)),
        "logit_err": float(np.linalg.norm(served - ref) / np.linalg.norm(ref)),
        "worst_row_err": float(row_err.max()),
        "top1_miss": float(np.mean(top != ref.argmax(axis=1))),
        "top1_gap": float(gap.max()),
    }


def check(cfg: dict, params, calib: np.ndarray, pool: np.ndarray,
          sample: List[Tuple[int, int, np.ndarray]]
          ) -> Tuple[bool, Dict[str, dict]]:
    """``(correct, {number: {"value", "limit"}})`` for the sampled requests,
    each ``(pool offset, size, served logits)``; ``limit`` is None for a
    number that is a reading only.  ``calib`` are the rows the program was
    calibrated on; the reference calibrates on them itself.  A sample that
    is empty or holds a malformed or non-finite answer is not correct, and
    so is a configuration with no limit."""
    limits = cfg.get("limits", {})
    served = [y for _, _, y in sample]
    ok = bool(sample) and all(
        isinstance(y, np.ndarray) and y.shape == (n, cfg["n_classes"])
        and np.isfinite(y).all() for (_, n, _), y in zip(sample, served))
    if not ok:
        return False, {k: {"value": None, "limit": limits.get(k)}
                       for k in NUMBERS}
    xs = np.concatenate([pool[o:o + n] for o, n, _ in sample])
    steps = reference.grid_steps(reference.act_fracs(
        cfg, reference.act_ranges(cfg, params, calib)))
    qw = reference.quantized_weights(cfg, params)
    # weights and grids are arguments, so one compiled reference serves
    # every seed
    fwd = jax.jit(lambda p, x: reference.forward(cfg, p, x))
    qfwd = jax.jit(lambda q, s, x: reference.quantized_forward(cfg, q, x, s))
    ref = _blocks(lambda x: fwd(params, x), xs)
    qref = _blocks(lambda x: qfwd(qw, steps, x), xs)
    numbers = compare(np.concatenate(served), ref, qref)
    out = {k: {"value": v, "limit": limits.get(k)} for k, v in numbers.items()}
    compared = [o for o in out.values() if o["limit"] is not None]
    correct = bool(compared) and all(o["value"] <= o["limit"]
                                     for o in compared)
    return correct, out
