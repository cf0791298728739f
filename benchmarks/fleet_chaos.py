"""Fleet chaos benchmark: replicated serving under injected faults.

Three :class:`~repro.runtime.serve.AccelServer` replicas front the SAME
shared :class:`~repro.quant.pack.PackedWeights` buffer (W8/W4/W2 point
executables from one ``qjax`` writer — replication multiplies pump threads,
not weight memory) behind a :class:`~repro.runtime.fleet.FleetRouter`.  A
burst of mixed-size requests is served while the chaos layer injects:

* a **pump-killing crash** on replica B mid-burst (a
  :class:`~repro.runtime.fleet.ReplicaCrash` escapes the per-batch
  containment and takes the whole pump thread down, like a segfaulting
  device runtime) — B must be ejected, healed via its factory after the
  cooldown, probed, and readmitted;
* a **latency-spike window** on replica C (schedule-driven delays through
  the generalized :class:`~repro.runtime.ft.FailureInjector`), driving the
  shared :class:`~repro.core.adaptive.BrownoutSelector` down the
  W8 -> W4/W2 ladder; a recovery tail of clean traffic must walk it back
  to W8.

Pass/fail criteria (reported, enforced with ``--check``):

* ZERO lost tickets: every submitted request resolves — success or typed
  failure — within its bound (no hung waiter);
* availability >= 99% over the whole run (retries/hedging mask the crash
  and the spikes);
* the crashed replica is readmitted after heal (``readmissions >= 1`` and
  a rebuilt server generation);
* the brownout trajectory is observable in fleet stats: at least one
  downshift during the spike window AND the fleet back at the top rung
  (W8) by the end of the recovery tail.

Emits machine-readable JSON via ``--out`` (default ``BENCH_fleet.json``) so
CI tracks the robustness trajectory.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict

import jax
import numpy as np

from repro.configs.mnist_cnn import CONFIG as CNN
from repro.core.adaptive import (BrownoutSelector, ServiceObjective,
                                 WorkingPoint, shared_point_executables)
from repro.core.flow import DesignFlow
from repro.core.reader import cnn_to_ir
from repro.models import cnn
from repro.quant.qtypes import DatatypeConfig
from repro.runtime.fleet import ChaosExecutable, FleetRouter
from repro.runtime.ft import FailureInjector
from repro.runtime.serve import AccelServer

MAX_BATCH = 8
POINTS = [WorkingPoint("w8", 8), WorkingPoint("w4", 4), WorkingPoint("w2", 2)]
TOP_RUNG = POINTS[0].name


def _build_points():
    """One qjax artifact; every replica's rungs read its ONE packed buffer."""
    params = cnn.init_params(CNN, jax.random.PRNGKey(0))
    graph = cnn_to_ir(CNN, {k: np.asarray(v) for k, v in params.items()})
    h, w = CNN.image_hw
    pool = np.asarray(jax.random.uniform(
        jax.random.PRNGKey(1), (MAX_BATCH, h, w, CNN.in_channels)))
    res = DesignFlow(graph).run(targets=("qjax",),
                                dtconfig=DatatypeConfig(16, 8),
                                calib_inputs=(pool,))
    pts = shared_point_executables(res.writers["qjax"], POINTS)
    return pts, pool


def _measure_base(exe, x) -> float:
    """Median warm per-batch latency — the yardstick every chaos magnitude
    and SLO threshold scales from, so the gate holds on any backend."""
    jax.block_until_ready(exe(x))            # compile
    samples = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(exe(x))
        samples.append(time.perf_counter() - t0)
    return max(float(np.median(samples)), 1e-4)


def run(full: bool = True) -> Dict:
    pts, pool = _build_points()
    base = _measure_base(pts[TOP_RUNG], pool)
    delay_s = max(20.0 * base, 0.25)         # an unmistakable spike
    n_burst = 90 if full else 36
    n_tail = 60 if full else 30

    slo = ServiceObjective(p95_latency_s=max(4.0 * base, 0.02),
                           window=12, min_samples=6, hold=6)
    brownout = BrownoutSelector(POINTS, slo)

    def server(wrap=lambda exe: exe):
        wrapped = {p.name: wrap(pts[p.name]) for p in POINTS}
        return AccelServer(wrapped[TOP_RUNG], max_batch=MAX_BATCH,
                           max_wait=0.002, point_executables=wrapped,
                           pipeline_depth=2)

    # replica B: generation 0 crashes its pump mid-burst; the healed
    # rebuild (generation 1+) is clean
    b_generation = [0]

    def factory_b():
        gen = b_generation[0]
        b_generation[0] += 1
        if gen == 0:
            counter = [0]
            return server(lambda exe: ChaosExecutable(
                exe, crash_at=[4], counter=counter))
        return server()

    # replica C: a windowed latency spike (calls 3..8 across its rungs)
    c_counter = [0]
    c_injector = FailureInjector(delay_at=list(range(3, 9)), delay_s=delay_s)

    def factory_c():
        return server(lambda exe: ChaosExecutable(
            exe, c_injector, counter=c_counter))

    router = FleetRouter(
        {"a": server, "b": factory_b, "c": factory_c},
        brownout=brownout,
        retries=3, backoff_s=0.005,
        hedge_after_s=max(8.0 * base, 0.1),
        default_deadline_s=120.0,
        probe=[pool[:1]],
        probe_interval_s=0.02,
        probe_timeout_s=delay_s + 10.0,
        heal_cooldown_s=0.2,
        seed=0)

    rng = np.random.default_rng(0)
    sizes = [int(s) for s in rng.choice([1, 1, 2, 2, 3, 4, 8], size=n_burst)]
    resolved_ok = resolved_err = 0
    t0 = time.perf_counter()
    with router:
        tickets = [router.submit(pool[:s]) for s in sizes]
        for t in tickets:
            try:
                t.result(timeout=120)
                resolved_ok += 1
            except TimeoutError:
                raise                        # a hung ticket fails the run
            except Exception:
                resolved_err += 1
        burst_wall = time.perf_counter() - t0
        min_rung = brownout.telemetry()["point"]

        # recovery tail: clean traffic walks the ladder back up and gives
        # the sentinel time to heal + readmit the crashed replica
        deadline = time.monotonic() + 60.0
        tail = 0
        while time.monotonic() < deadline:
            tk = router.submit(pool[:2])
            try:
                tk.result(timeout=120)
                resolved_ok += 1
            except TimeoutError:
                raise
            except Exception:
                resolved_err += 1
            tail += 1
            stats = router.stats()
            recovered = stats["brownout"]["point"] == TOP_RUNG
            readmitted = stats["replicas"]["b"]["readmissions"] >= 1
            if tail >= n_tail and recovered and readmitted:
                break
        stats = router.stats()
    wall = time.perf_counter() - t0

    submitted = n_burst + tail
    trajectory = stats["brownout"]["shifts"]
    return {
        "mode": "fleet_chaos",
        "replicas": len(stats["replicas"]),
        "submitted": submitted,
        "resolved_ok": resolved_ok,
        "resolved_err": resolved_err,
        "lost": submitted - resolved_ok - resolved_err,
        "availability": round(stats["availability"], 4),
        "retries": stats["retries"],
        "hedges": stats["hedges"],
        "hedge_wins": stats["hedge_wins"],
        "probes": stats["probes"],
        "burst_wall_s": round(burst_wall, 3),
        "wall_s": round(wall, 3),
        "base_latency_ms": round(base * 1e3, 3),
        "injected_delay_ms": round(delay_s * 1e3, 1),
        "injected_delays": c_injector.injected_delays,
        "b_ejections": stats["replicas"]["b"]["ejections"],
        "b_readmissions": stats["replicas"]["b"]["readmissions"],
        "b_generation": stats["replicas"]["b"]["generation"],
        "brownout_trajectory": trajectory,
        "brownout_min_rung": min_rung,
        "brownout_final": stats["brownout"]["point"],
    }


def evaluate(row: Dict) -> Dict:
    zero_lost = row["lost"] == 0
    avail_ok = row["availability"] >= 0.99
    readmit_ok = (row["b_readmissions"] >= 1 and row["b_ejections"] >= 1
                  and row["b_generation"] >= 2)
    names = [p.name for p in POINTS]
    downs = [s for s in row["brownout_trajectory"]
             if names.index(s[1]) > names.index(s[0])]
    brownout_ok = bool(downs) and row["brownout_final"] == TOP_RUNG
    return {
        "pass": zero_lost and avail_ok and readmit_ok and brownout_ok,
        "zero_lost": zero_lost,
        "availability_ok": avail_ok,
        "availability": row["availability"],
        "readmit_ok": readmit_ok,
        "brownout_ok": brownout_ok,
        "downshifts": len(downs),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="36-request burst")
    ap.add_argument("--out", default="BENCH_fleet.json",
                    help="JSON output path")
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero when a chaos criterion fails")
    args = ap.parse_args()
    row = run(full=not args.quick)
    print("fleet_chaos," + ",".join(
        f"{k}={v}" for k, v in row.items() if not k.startswith("_")))
    crit = evaluate(row)
    print("fleet_chaos,mode=criterion,"
          + ",".join(f"{k}={v}" for k, v in crit.items()))
    doc = {
        "backend": jax.default_backend(),
        "quick": args.quick,
        "row": {k: v for k, v in row.items() if not k.startswith("_")},
        "criterion": crit,
    }
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"# wrote {args.out}")
    if args.check and not crit["pass"]:
        raise SystemExit(f"fleet chaos criterion failed: {crit}")


if __name__ == "__main__":
    from repro.caches import enable_compile_cache
    enable_compile_cache()
    main()
