"""Serving-throughput benchmark: naive vs caller-pumped vs async-pumped.

A mixed-size request stream is served from identical batch-polymorphic
artifacts (the paper's one-accelerator-serves-evolving-workloads story):

* ``naive``      — every request executes alone, at its own size; each
  distinct size costs a trace and every request pays full dispatch overhead.
* ``sync_pump``  — the :class:`~repro.runtime.serve.AccelServer` packs
  requests up to ``max_batch``, pads to LRU-aligned buckets and slices
  results back per request; the caller thread drives ``pump()``.
* ``async_pump`` — same server with the background pump thread
  (``start()``): ``submit`` returns tickets immediately and host batch
  assembly overlaps device execution (``pipeline_depth`` batches stay
  dispatched-but-unforced).

A second section serves a two-tenant burst (weighted round-robin 2:1) and
reports per-tenant p50/p95 with the measured-latency bucket policy active
(``bucket_latency_s`` is the per-bucket execution EWMA the policy consults;
the static ladder heuristic only handles cold start).

Pass/fail criteria (reported, enforced with ``--check``):

* async_pump >= 1.3x sync_pump requests/s on the burst-backlog workload on
  a compiled backend (parity within 10% on the CPU reference backend, where
  the overlap window is bounded by host compute);
* both tenants report latency percentiles and a warm bucket-latency model.
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List

import jax
import numpy as np

from repro.configs.mnist_cnn import CONFIG as CNN
from repro.core.flow import DesignFlow
from repro.core.reader import cnn_to_ir
from repro.models import cnn
from repro.runtime.scheduler import percentile
from repro.runtime.serve import AccelServer

MAX_BATCH = 8


def _stream(n: int, rng) -> List[int]:
    """Mixed request sizes, skewed small (edge traffic: mostly singles)."""
    return [int(s) for s in rng.choice([1, 1, 1, 2, 2, 3, 4, 5, 8], size=n)]


def _row(
    mode: str, n: int, wall: float, lat: List[float], exe, padding_waste: float
) -> Dict:
    tel = exe.telemetry()
    return {
        "mode": mode,
        "requests": n,
        "req_per_s": round(n / wall, 1),
        "p50_ms": round(percentile(lat, 0.50) * 1e3, 2),
        "p95_ms": round(percentile(lat, 0.95) * 1e3, 2),
        "padding_waste": round(padding_waste, 3),
        "hit_rate": round(tel["hit_rate"], 3),
        "traces": tel["misses"],
    }


def _artifact(flow: DesignFlow):
    return flow.run().batched["jax"]


def run(full: bool = True) -> List[Dict]:
    rng = np.random.default_rng(0)
    params = cnn.init_params(CNN, jax.random.PRNGKey(0))
    graph = cnn_to_ir(CNN, {k: np.asarray(v) for k, v in params.items()})
    flow = DesignFlow(graph)
    n = 96 if full else 24
    sizes = _stream(n, rng)
    h, w = CNN.image_hw
    pool = np.asarray(
        jax.random.uniform(
            jax.random.PRNGKey(1), (MAX_BATCH, h, w, CNN.in_channels)
        )
    )
    xs = [pool[:s] for s in sizes]

    # Arrival model: a burst — all n requests are queued when serving starts
    # (the backlogged-server regime where scheduling policy matters; with an
    # idle server all modes degenerate to per-request execution).  Latency
    # is completion time since the burst for every mode.

    # naive: per-request FIFO execution on a fresh artifact (no coalescing)
    naive_exe = _artifact(flow)
    lat, t0 = [], time.perf_counter()
    for x in xs:
        jax.block_until_ready(naive_exe(x))
        lat.append(time.perf_counter() - t0)
    naive = _row("naive", n, time.perf_counter() - t0, lat, naive_exe, 0.0)

    # sync_pump: the server packs the backlog; the caller drives the pump
    srv = AccelServer(
        _artifact(flow), max_batch=MAX_BATCH, max_wait=0.001, queue_depth=n
    )
    t0 = time.perf_counter()
    tickets = [srv.submit(x) for x in xs]
    srv.pump(flush=True)  # drain the backlog (tail included)
    for t in tickets:
        jax.block_until_ready(srv.result(t))
    wall = time.perf_counter() - t0
    stats = srv.stats()
    sync = _row(
        "sync_pump", n, wall, srv.latencies, srv.executable, stats["padding_waste"]
    )
    sync["batches"] = stats["executed_batches"]

    # async_pump: background thread assembles/dispatches while the caller is
    # still submitting and while earlier batches execute on the device
    asrv = AccelServer(
        _artifact(flow),
        max_batch=MAX_BATCH,
        max_wait=0.001,
        queue_depth=n,
        pipeline_depth=3,
    )
    with asrv:
        t0 = time.perf_counter()
        tickets = [asrv.submit(x) for x in xs]
        for t in tickets:
            t.result(timeout=120)
        wall = time.perf_counter() - t0
        stats = asrv.stats()
        arow = _row(
            "async_pump",
            n,
            wall,
            asrv.latencies,
            asrv.executable,
            stats["padding_waste"],
        )
        arow["batches"] = stats["executed_batches"]
    return [naive, sync, arow]


def run_two_tenant(full: bool = True) -> Dict:
    """Two resident graphs multiplexed on one device, WRR 2:1, measured
    bucket policy active; returns the per-tenant stats breakdown."""
    rng = np.random.default_rng(7)
    params = cnn.init_params(CNN, jax.random.PRNGKey(0))
    graph = cnn_to_ir(CNN, {k: np.asarray(v) for k, v in params.items()})
    flow = DesignFlow(graph)
    n = 48 if full else 16
    h, w = CNN.image_hw
    pool = np.asarray(
        jax.random.uniform(
            jax.random.PRNGKey(2), (MAX_BATCH, h, w, CNN.in_channels)
        )
    )
    srv = AccelServer(max_batch=MAX_BATCH, max_wait=0.001)
    srv.add_tenant(
        "interactive",
        _artifact(flow),
        max_batch=MAX_BATCH,
        max_wait=0.001,
        queue_depth=2 * n,
        weight=2,
    )
    srv.add_tenant(
        "bulk",
        _artifact(flow),
        max_batch=MAX_BATCH,
        max_wait=0.001,
        queue_depth=2 * n,
        weight=1,
    )
    with srv:
        tickets = [
            srv.submit(pool[: int(s)], tenant=name)
            for s in _stream(n, rng)
            for name in ("interactive", "bulk")
        ]
        for t in tickets:
            t.result(timeout=120)
    agg = srv.stats()
    out = {"mode": "two_tenant", "requests": 2 * n}
    for name, s in agg["tenants"].items():
        out[f"{name}_p50_ms"] = round(s.get("p50_latency_s", 0.0) * 1e3, 2)
        out[f"{name}_p95_ms"] = round(s.get("p95_latency_s", 0.0) * 1e3, 2)
        out[f"{name}_weight"] = s["weight"]
        # warm EWMA entries == the measured bucket policy is live (the
        # ladder heuristic only covers buckets with no measurement yet)
        out[f"{name}_measured_buckets"] = len(s["bucket_latency_s"])
    return out


def evaluate(rows: List[Dict], two_tenant: Dict) -> Dict:
    sync = next(r for r in rows if r["mode"] == "sync_pump")
    arow = next(r for r in rows if r["mode"] == "async_pump")
    ratio = arow["req_per_s"] / max(sync["req_per_s"], 1e-9)
    backend = jax.default_backend()
    # on a compiled backend the pump overlaps host assembly with device
    # execution; the CPU reference backend shares those cycles, so the bar
    # there is parity (the async path must not cost throughput)
    target = 1.3 if backend != "cpu" else 0.9
    measured = [v for k, v in two_tenant.items() if k.endswith("_measured_buckets")]
    percentiles = [v for k, v in two_tenant.items() if k.endswith("_p95_ms")]
    tenants_ok = (
        len(measured) == 2
        and all(m >= 1 for m in measured)
        and all(p > 0 for p in percentiles)
    )
    return {
        "pass": ratio >= target and tenants_ok,
        "backend": backend,
        "async_vs_sync": round(ratio, 2),
        "target": target,
        "tenants_ok": tenants_ok,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="24-request stream")
    ap.add_argument("--out", default="BENCH_serve.json", help="JSON output path")
    ap.add_argument(
        "--check",
        action="store_true",
        help="exit non-zero when the async-vs-sync criterion fails",
    )
    args = ap.parse_args()
    rows = run(full=not args.quick)
    two = run_two_tenant(full=not args.quick)
    for r in rows + [two]:
        print("serve_throughput," + ",".join(f"{k}={v}" for k, v in r.items()))
    naive, sync, arow = rows
    speedup = sync["req_per_s"] / max(naive["req_per_s"], 1e-9)
    print(f"serve_throughput,mode=summary,coalesced_speedup={speedup:.2f}x")
    crit = evaluate(rows, two)
    print(
        "serve_throughput,mode=criterion,"
        + ",".join(f"{k}={v}" for k, v in crit.items())
    )
    doc = {
        "backend": jax.default_backend(),
        "quick": args.quick,
        "rows": rows,
        "two_tenant": two,
        "criterion": crit,
    }
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"# wrote {args.out}")
    if args.check and not crit["pass"]:
        raise SystemExit(f"serve throughput criterion failed: {crit}")


if __name__ == "__main__":
    from repro.caches import enable_compile_cache
    enable_compile_cache()
    main()
