"""Async multi-tenant accelerator serving demo.

    PYTHONPATH=src python examples/serving_demo.py [--requests 24]

Two tenants share one device through the async :class:`AccelServer` (the
paper's CPS scenario scaled up: one reconfigurable accelerator, several
resident workloads, runtime precision adaptation):

1. each tenant registers its own graph + bounded queue + QoS weight —
   ``interactive`` (weight 2, tight p95 SLO) and ``bulk`` (weight 1, relaxed
   SLO); the background pump thread serves both via weighted round-robin,
2. ``submit()`` returns a future-style ticket immediately; the pump
   coalesces requests into bucket-sized batches aligned with each
   executable's LRU of traced shapes (pad-to-bucket, slice-back),
3. every completed request feeds its latency into the tenant's SLO
   controller, which walks the W8/W4/W2 precision ladder — downshift when
   the windowed p95 violates the SLO, recover when there is headroom — and
   every batch feeds its execution time into the measured bucket policy,
4. per-request results are demuxed back to their tickets, and the server
   reports per-tenant throughput, latency percentiles, precision shifts and
   the per-bucket latency model.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.configs.mnist_cnn import CONFIG as CNN
from repro.core.adaptive import RuntimePolicy, WorkingPoint
from repro.core.flow import DesignFlow
from repro.core.reader import cnn_to_ir
from repro.models import cnn
from repro.quant.qtypes import DatatypeConfig
from repro.runtime.serve import AccelServer, ServiceObjective


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=24, help="requests per tenant")
    ap.add_argument("--max-batch", type=int, default=8)
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    params = cnn.init_params(CNN, jax.random.PRNGKey(0))
    graph = cnn_to_ir(CNN, {k: np.asarray(v) for k, v in params.items()})
    flow = DesignFlow(graph)
    h, w = CNN.image_hw
    pool = np.asarray(
        jax.random.uniform(
            jax.random.PRNGKey(1), (args.max_batch, h, w, CNN.in_channels)
        )
    )

    # working points: one graph, three precision builds (W8/W4/W2 weights);
    # both tenants share the same executables — switching points re-builds
    # nothing, so the SLO controllers just pick different entries
    points = [WorkingPoint("w8", 8), WorkingPoint("w4", 4), WorkingPoint("w2", 2)]
    point_exes = {}
    for pt in points:
        res = flow.run(
            dtconfig=DatatypeConfig(16, pt.weight_bits), calib_inputs=(pool,)
        )
        point_exes[pt.name] = res.batched["jax"]

    # two tenants, two contracts: interactive wants low p95 and gets 2x the
    # device share; bulk tolerates latency and takes the leftover slots
    srv = AccelServer(max_batch=args.max_batch, max_wait=0.002)
    for name, weight, p95_ms in (("interactive", 2, 40.0), ("bulk", 1, 400.0)):
        srv.add_tenant(
            name,
            point_exes["w8"],
            max_batch=args.max_batch,
            max_wait=0.002,
            policy=RuntimePolicy(points),
            point_executables=point_exes,
            weight=weight,
            slo=ServiceObjective(
                p95_latency_s=p95_ms / 1e3, window=8, min_samples=4, hold=4
            ),
        )
    print(
        f"serving {args.requests} mixed-size requests per tenant through "
        f"two resident graphs (WRR 2:1, max_batch={args.max_batch})"
    )

    # the stream: both tenants burst at once; tickets resolve as the
    # background pump drains the queues
    sizes = rng.choice([1, 1, 2, 2, 3, 4, 8], size=args.requests)
    with srv:  # start() the pump; stop(drain=True) on exit
        tickets = [
            (srv.submit(pool[: int(size)], tenant=name), name, int(size))
            for size in sizes
            for name in ("interactive", "bulk")
        ]
        for ticket, name, size in tickets:
            y = ticket.result(timeout=120)
            assert y.shape[0] == size
    print(f"all {len(tickets)} tickets answered with their own rows")

    stats = srv.stats()
    for name, s in stats["tenants"].items():
        slo = s["slo"]
        print(
            f"{name}: {s['executed_batches']} batches for {s['submitted']} "
            f"requests | weight {s['weight']} | p50 "
            f"{s.get('p50_latency_s', 0.0) * 1e3:.1f}ms p95 "
            f"{s.get('p95_latency_s', 0.0) * 1e3:.1f}ms (SLO "
            f"{slo['p95_slo_s'] * 1e3:.0f}ms) | point {slo['point']} | "
            f"shifts {slo['shifts']} | points served {s['points']}"
        )
        buckets = {
            b: f"{t * 1e3:.1f}ms"
            for b, t in sorted(s["bucket_latency_s"].items())
        }
        print(f"{name}: measured bucket latency {buckets}")
    print(
        f"total: {stats['executed_batches']} batches | padding waste "
        f"{stats['padding_waste']:.1%} | p95 "
        f"{stats.get('p95_latency_s', 0.0) * 1e3:.1f}ms"
    )


if __name__ == "__main__":
    from repro.caches import enable_compile_cache
    enable_compile_cache()
    main()
