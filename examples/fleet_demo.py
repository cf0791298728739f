"""Fault-tolerant fleet serving demo.

    PYTHONPATH=src python examples/fleet_demo.py [--requests 40]
    PYTHONPATH=src python examples/fleet_demo.py --soak --seconds 10 --seed 7

Three :class:`AccelServer` replicas — each its own pump thread, all serving
W8/W4/W2 point executables over the SAME shared packed-weight buffer —
behind a :class:`FleetRouter`:

1. the health layer heartbeats every replica (EWMA latency/error scoring,
   circuit breakers, straggler watchdog) and walks the
   healthy -> suspect -> ejected -> probing -> readmitted state machine;
2. chaos is injected mid-run: one replica's pump is crashed outright and
   another gets a latency-spike window — requests fail over with bounded
   backoff+jitter retries and tail-latency hedging, so the burst completes
   with zero lost tickets;
3. the crashed replica is healed (its factory rebuilds a fresh server)
   after a cooldown, canary-probed, and readmitted;
4. a fleet-level brownout selector degrades the WHOLE fleet down the
   precision ladder when aggregate p95/backlog breaches the objective and
   restores W8 on recovery.

``--soak`` runs a seeded, time-bounded chaos soak instead: probabilistic
failures and delays (the generalized ``FailureInjector`` rate modes) are
injected continuously and the run asserts zero lost tickets at the end —
the CI smoke uses this mode.
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

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


def build_points():
    params = cnn.init_params(CNN, jax.random.PRNGKey(0))
    graph = cnn_to_ir(CNN, {k: np.asarray(v) for k, v in params.items()})
    h, w = CNN.image_hw
    pool = np.asarray(jax.random.uniform(
        jax.random.PRNGKey(1), (MAX_BATCH, h, w, CNN.in_channels)))
    res = DesignFlow(graph).run(targets=("qjax",),
                                dtconfig=DatatypeConfig(16, 8),
                                calib_inputs=(pool,))
    return shared_point_executables(res.writers["qjax"], POINTS), pool


def make_server(pts, wrap=lambda exe: exe):
    wrapped = {p.name: wrap(pts[p.name]) for p in POINTS}
    return AccelServer(wrapped["w8"], max_batch=MAX_BATCH, max_wait=0.002,
                       point_executables=wrapped)


def print_fleet(stats):
    print(f"  availability={stats['availability']:.4f} "
          f"succeeded={stats['succeeded']} failed={stats['failed']} "
          f"retries={stats['retries']} hedges={stats['hedges']} "
          f"shed={stats['shed']}")
    for name, rep in stats["replicas"].items():
        print(f"  replica {name}: state={rep['state']} "
              f"served={rep['served']} failures={rep['failures']} "
              f"ejections={rep['ejections']} "
              f"readmissions={rep['readmissions']} gen={rep['generation']}")
    if "brownout" in stats:
        b = stats["brownout"]
        print(f"  brownout: point={b['point']} shifts={b['shifts']}")


def demo(args):
    pts, pool = build_points()
    brownout = BrownoutSelector(
        POINTS, ServiceObjective(p95_latency_s=0.05, window=12,
                                 min_samples=6, hold=6))

    killer = ChaosExecutable(pts["w8"], crash_at=[3])
    spikes = FailureInjector(delay_at=list(range(2, 7)), delay_s=0.3)
    spike_counter = [0]

    router = FleetRouter(
        {"a": lambda: make_server(pts),
         "b": lambda: make_server(
             {**pts, "w8": killer} if killer.calls == 0 else pts),
         "c": lambda: make_server(pts, lambda exe: ChaosExecutable(
             exe, spikes, counter=spike_counter))},
        brownout=brownout, retries=3, backoff_s=0.005, hedge_after_s=0.1,
        probe=[pool[:1]], probe_interval_s=0.02, heal_cooldown_s=0.2,
        default_deadline_s=60.0)

    rng = np.random.default_rng(0)
    print(f"== burst of {args.requests} requests with a pump crash on 'b' "
          "and latency spikes on 'c' ==")
    with router:
        tickets = [router.submit(pool[:int(s)])
                   for s in rng.choice([1, 2, 2, 4, 8], size=args.requests)]
        ok = err = 0
        for t in tickets:
            try:
                t.result(timeout=120)
                ok += 1
            except Exception as e:
                err += 1
                print(f"  typed failure: {type(e).__name__}: {e}")
        print(f"== burst done: {ok} ok, {err} typed failures, 0 hung ==")
        # clean tail: heal + readmit 'b', recover the precision ladder
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            router.submit(pool[:2]).result(timeout=120)
            s = router.stats()
            if (s["replicas"]["b"]["readmissions"] >= 1
                    and s["brownout"]["point"] == "w8"):
                break
        print("== after recovery tail ==")
        print_fleet(router.stats())


def soak(args):
    pts, pool = build_points()
    inj = FailureInjector(rate=args.fail_rate, seed=args.seed,
                          delay_rate=args.delay_rate, delay_s=0.05)
    counter = [0]
    router = FleetRouter(
        {"a": lambda: make_server(pts),
         "b": lambda: make_server(pts, lambda exe: ChaosExecutable(
             exe, inj, counter=counter)),
         "c": lambda: make_server(pts)},
        retries=3, backoff_s=0.005, probe=[pool[:1]],
        probe_interval_s=0.02, heal_cooldown_s=0.1,
        default_deadline_s=60.0, seed=args.seed)

    rng = np.random.default_rng(args.seed)
    t_end = time.monotonic() + args.seconds
    submitted = ok = err = 0
    print(f"== seeded chaos soak: {args.seconds}s, fail_rate="
          f"{args.fail_rate}, delay_rate={args.delay_rate}, "
          f"seed={args.seed} ==")
    with router:
        while time.monotonic() < t_end:
            tickets = [router.submit(pool[:int(s)])
                       for s in rng.choice([1, 2, 4, 8], size=8)]
            submitted += len(tickets)
            for t in tickets:
                try:
                    t.result(timeout=120)
                    ok += 1
                except Exception:
                    err += 1
        stats = router.stats()
    lost = submitted - ok - err
    print(f"== soak done: submitted={submitted} ok={ok} "
          f"typed_failures={err} lost={lost} "
          f"injected_failures={inj.injected_failures} "
          f"injected_delays={inj.injected_delays} ==")
    print_fleet(stats)
    if lost != 0:
        raise SystemExit(f"soak lost {lost} tickets")
    print("zero lost tickets: every request resolved")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=40)
    ap.add_argument("--soak", action="store_true",
                    help="seeded time-bounded chaos soak (CI smoke mode)")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fail-rate", type=float, default=0.05)
    ap.add_argument("--delay-rate", type=float, default=0.05)
    args = ap.parse_args()
    if args.soak:
        soak(args)
    else:
        demo(args)


if __name__ == "__main__":
    from repro.caches import enable_compile_cache
    enable_compile_cache()
    main()
