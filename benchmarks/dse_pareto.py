"""Design-space exploration benchmark: Pareto fronts under resource budgets.

For each Table-I CNN topology (``mnist-cnn``, ``separable-cnn``) the
:class:`~repro.dse.DesignSpaceExplorer` runs twice:

* **unconstrained** — the full front the runtime ladder can walk (W8/W4/W2
  rungs costed in the roofline byte/latency terms, scored by top-1
  agreement against the float reference on the calibration batch);
* **constrained** — a ``weight_bytes`` ceiling placed strictly below the
  unconstrained front's top point, so the explorer must drop W8 and re-pick
  its compile configuration under the tightened budget.

Pass/fail criteria (reported, enforced with ``--check``):

* every front is non-empty and serializes/round-trips through JSON;
* the unconstrained front keeps >= 3 mutually non-dominated points (the
  adaptive ladder has somewhere to go);
* the constrained front's maximum weight bytes are strictly smaller than
  the unconstrained front's (the ceiling actually binds);
* each point's ``weight_bytes`` equals the packed-buffer accounting
  (``PackedWeights.view_bytes`` with the front's per-layer caps) — the
  predicted-bytes terms stay tied to the measured substrate.

Emits machine-readable JSON via ``--out`` (default ``BENCH_dse.json``).
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List

import jax
import numpy as np

from repro.configs.mnist_cnn import CONFIG as CNN
from repro.configs.separable_cnn import CONFIG as SEP
from repro.core.flow import DesignFlow
from repro.core.reader import cnn_to_ir, separable_cnn_to_ir
from repro.dse import ParetoFront, ResourceBudget
from repro.models import cnn

CALIB_ROWS_FULL = 64
CALIB_ROWS_QUICK = 32


def _topologies():
    params = cnn.init_params(CNN, jax.random.PRNGKey(0))
    g = cnn_to_ir(CNN, {k: np.asarray(v) for k, v in params.items()})
    yield "mnist-cnn", g, (CNN.image_hw[0], CNN.image_hw[1], CNN.in_channels)

    sep_params = cnn.init_separable_params(SEP, jax.random.PRNGKey(1))
    g_sep = separable_cnn_to_ir(
        SEP, {k: np.asarray(v) for k, v in sep_params.items()})
    yield ("separable-cnn", g_sep,
           (SEP.image_hw[0], SEP.image_hw[1], SEP.in_channels))


def _front_row(name: str, kind: str, front: ParetoFront,
               explore_s: float) -> Dict:
    return {
        "topology": name, "run": kind,
        "n_points": len(front),
        "points": "/".join(p.point.name for p in front.points),
        "max_weight_bytes": max(p.weight_bytes for p in front.points),
        "total_bytes": max(p.total_bytes for p in front.points),
        "fifo_slack": front.fifo_slack,
        "act_bits": front.act_bits,
        "agreement": "/".join(f"{p.agreement:.3f}" for p in front.points),
        "explore_s": round(explore_s, 3),
    }


def run(full: bool = True) -> List[Dict]:
    rng = np.random.default_rng(0)
    rows: List[Dict] = []
    n = CALIB_ROWS_FULL if full else CALIB_ROWS_QUICK
    for name, graph, item_shape in _topologies():
        calib = rng.random((n, *item_shape), np.float32)
        flow = DesignFlow(graph)

        t0 = time.perf_counter()
        free = flow.explore((calib,))
        t_free = time.perf_counter() - t0
        rows.append(_front_row(name, "unconstrained", free, t_free))

        # ceiling strictly below the free front's top point: W8 must fall off
        ceiling = max(p.weight_bytes for p in free.points) - 1
        t0 = time.perf_counter()
        tight = flow.explore((calib,),
                             budget=ResourceBudget(weight_bytes=ceiling))
        t_tight = time.perf_counter() - t0
        row = _front_row(name, "constrained", tight, t_tight)
        row["weight_bytes_ceiling"] = ceiling
        rows.append(row)

        # predicted-bytes terms must match the packed-substrate accounting
        writer = flow.run(("qjax",), calib_inputs=(calib,),
                          **free.run_kwargs()).writers["qjax"]
        caps = free.per_layer_bits
        rows[-2]["bytes_match"] = all(
            p.weight_bytes == writer.packed.view_bytes(p.point.weight_bits,
                                                       caps=caps)
            for p in free.points)

        # fronts must survive serialization (what CI artifacts/serving load)
        rows[-2]["roundtrip"] = (
            ParetoFront.from_json(free.to_json()).to_json() == free.to_json())
    return rows


def evaluate(rows: List[Dict]) -> Dict:
    by = {(r["topology"], r["run"]): r for r in rows}
    checks = {}
    ok = True
    for name in ("mnist-cnn", "separable-cnn"):
        free = by.get((name, "unconstrained"))
        tight = by.get((name, "constrained"))
        if free is None or tight is None:
            return {"pass": False, "reason": f"missing rows for {name}"}
        c = {
            "front_nonempty": free["n_points"] > 0 and tight["n_points"] > 0,
            "free_points_ge_3": free["n_points"] >= 3,
            "constrained_smaller": (tight["max_weight_bytes"]
                                    < free["max_weight_bytes"]),
            "bytes_match": bool(free.get("bytes_match")),
            "roundtrip": bool(free.get("roundtrip")),
        }
        ok = ok and all(c.values())
        checks[name] = c
    return {"pass": ok, **{f"{n}.{k}": v for n, cs in checks.items()
                           for k, v in cs.items()}}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller calibration batch (CI smoke)")
    ap.add_argument("--out", default="BENCH_dse.json",
                    help="machine-readable JSON output path")
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero when a front criterion fails")
    args = ap.parse_args()
    rows = run(full=not args.quick)
    for r in rows:
        print("dse_pareto," + ",".join(f"{k}={v}" for k, v in r.items()))
    crit = evaluate(rows)
    print("dse_pareto,mode=criterion,"
          + ",".join(f"{k}={v}" for k, v in crit.items()))
    doc = {"backend": jax.default_backend(), "quick": args.quick,
           "rows": rows, "criterion": crit}
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"# wrote {args.out}")
    if args.check and not crit["pass"]:
        raise SystemExit(f"dse_pareto criterion failed: {crit}")


if __name__ == "__main__":
    from repro.caches import enable_compile_cache
    enable_compile_cache()
    main()
