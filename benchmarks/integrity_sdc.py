"""Weight-memory SDC benchmark: bit-flip chaos against the integrity layer.

Three :class:`~repro.runtime.serve.AccelServer` replicas serve W8/W4/W2
point executables over the SAME shared
:class:`~repro.quant.pack.PackedWeights` buffer behind a
:class:`~repro.runtime.fleet.FleetRouter` with semantic canaries, while a
seeded :class:`~repro.runtime.integrity.BitFlipInjector` corrupts the live
buffers and each replica runs a rate-bounded
:class:`~repro.runtime.integrity.Scrubber` over them:

* **phase A — repairable SEUs**: single-bit flips in cached W4/W2 packed
  views land mid-traffic (alongside a pump-killing crash on replica B —
  combined bit-flip + crash chaos).  Every flip must be detected and the
  view re-derived BIT-EXACTLY from the intact master codes within the scrub
  window, with no server restart;
* **phase B — unrepairable SEU**: a flip in the int8 master codes.  Every
  scrubber quarantines, every pump dies with a typed
  :class:`~repro.runtime.integrity.IntegrityError` (zero post-detection
  results served from the poisoned buffer), the sentinel ejects each
  replica with a ``quarantined`` cause and heals through the factories,
  which restore the master from a pristine copy — the fleet readmits and
  serving resumes.

Every successful result over the whole run is compared against golden
outputs captured before any chaos; a mismatch counts as a *corrupted
result served* and fails the run.

Pass/fail criteria (reported, enforced with ``--check``):

* every injected flip detected within ``WINDOW_PASSES`` scrub passes;
* ZERO corrupted results served (post-detection or otherwise);
* every W4/W2 view repair round-trips bit-exactly from the master codes;
* the master-code flip ends in ``quarantined`` ejections and a healed,
  fully readmitted fleet;
* availability >= 0.99 over the whole run (bit-flip + crash chaos).

Emits machine-readable JSON via ``--out`` (default ``BENCH_integrity.json``).
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.mnist_cnn import CONFIG as CNN
from repro.core.adaptive import WorkingPoint, shared_point_executables
from repro.core.flow import DesignFlow
from repro.core.reader import cnn_to_ir
from repro.models import cnn
from repro.quant.qtypes import DatatypeConfig
from repro.runtime.fleet import (ChaosExecutable, FleetRouter, HealthState,
                                 NoReplicaAvailable)
from repro.runtime.integrity import BitFlipInjector, CanarySet, Scrubber
from repro.runtime.serve import AccelServer

MAX_BATCH = 8
POINTS = [WorkingPoint("w8", 8), WorkingPoint("w4", 4), WorkingPoint("w2", 2)]
TOP_RUNG = POINTS[0].name
SIZES = (1, 2, 4)
WINDOW_PASSES = 6          # detection bound, in full scrub passes
SCRUB_RATE = 20e6          # bytes/sec — far above the tiny CNN's period
SCRUB_INTERVAL = 0.002


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


def _golden_outputs(pts, pool) -> Dict[str, Dict[int, np.ndarray]]:
    """Known-good outputs per point per request size, captured before any
    chaos — the yardstick every served result is checked against."""
    return {name: {s: np.asarray(exe(pool[:s])) for s in SIZES}
            for name, exe in pts.items()}


def _matches(golden, size: int, val) -> bool:
    out = np.asarray(val[0] if isinstance(val, tuple) else val)
    return any(np.allclose(out, g[size], rtol=1e-4, atol=1e-5)
               for g in golden.values())


def run(full: bool = True) -> Dict:
    pts, pool = _build_points()
    packed = pts[TOP_RUNG].packed          # the ONE shared buffer
    for t in packed.tensors.values():      # derive the sub-byte view regions
        t.packed_view(4)
        t.packed_view(2)
    golden = _golden_outputs(pts, pool)
    golden_codes = {n: np.array(t.codes) for n, t in packed.tensors.items()}
    golden_scale = {n: np.array(t.scale) for n, t in packed.tensors.items()}
    golden_views = {(n, bits, align): np.array(buf)
                    for n, t in packed.tensors.items()
                    for (bits, align), buf in t._packed.items()}

    def restore_master():
        """Heal-path weight restore: pristine master + re-derived views."""
        for n, t in packed.tensors.items():
            t.codes = jnp.asarray(golden_codes[n])
            t.scale = jnp.asarray(golden_scale[n])
            t.seal()
            for (bits, align) in list(t._packed):
                t.repair_view(bits, align=align)

    scrubbers: List[Scrubber] = []         # every scrubber ever started
    live_scrub: Dict[str, Scrubber] = {}

    def make_factory(name: str, wrap=None):
        def factory():
            if packed.verify():            # healing a quarantined buffer:
                restore_master()           # restore before serving again
            mk = wrap if wrap is not None else (lambda exe: exe)
            wrapped = {p.name: mk(pts[p.name]) for p in POINTS}
            srv = AccelServer(wrapped[TOP_RUNG], max_batch=MAX_BATCH,
                              max_wait=0.002, point_executables=wrapped,
                              pipeline_depth=2)
            old = live_scrub.pop(name, None)
            if old is not None:
                old.stop()
            sc = Scrubber(packed, rate_bytes_s=SCRUB_RATE,
                          interval_s=SCRUB_INTERVAL)
            sc.tag = f"{name}:{len(scrubbers)}"      # forensics in the row
            srv.attach_scrubber(sc)
            sc.start()
            scrubbers.append(sc)
            live_scrub[name] = sc
            return srv
        return factory

    # replica B: generation 0 crashes its pump mid-run (fail-stop chaos
    # riding alongside the bit-flip chaos); healed rebuilds are clean
    b_generation = [0]
    b_counter = [0]

    def factory_b():
        gen = b_generation[0]
        b_generation[0] += 1
        wrap = (lambda exe: ChaosExecutable(exe, crash_at=[5],
                                            counter=b_counter)
                ) if gen == 0 else None
        return make_factory("b", wrap=wrap)()

    canaries = CanarySet.capture(pts, [(pool[:1],)], k=1,
                                 rtol=1e-3, atol=1e-4)
    router = FleetRouter(
        {"a": make_factory("a"), "b": factory_b, "c": make_factory("c")},
        retries=3, backoff_s=0.005,
        default_deadline_s=60.0,
        canaries=canaries,
        probe_interval_s=0.02,
        probe_timeout_s=10.0,
        heal_cooldown_s=0.2,
        seed=0)

    rng = np.random.default_rng(0)
    injector = BitFlipInjector(packed, seed=1, kinds=("view",))
    n_view_flips = 5 if full else 2
    per_flip_traffic = 12 if full else 6
    counters = {"ok": 0, "err": 0, "shed": 0, "corrupted": 0}

    def serve(n: int) -> None:
        tickets = []
        for _ in range(n):
            s = int(rng.choice(SIZES))
            try:
                tickets.append((s, router.submit(pool[:s])))
            except (NoReplicaAvailable, RuntimeError):
                counters["shed"] += 1
        for s, tk in tickets:
            try:
                val = tk.result(timeout=60)
            except TimeoutError:
                raise                      # a hung ticket fails the run
            except Exception:
                counters["err"] += 1
                continue
            counters["ok"] += 1
            if not _matches(golden, s, val):
                counters["corrupted"] += 1

    def passes() -> int:
        return max((sc.scrub_passes for sc in scrubbers), default=0)

    flips = []
    t0 = time.perf_counter()
    with router:
        serve(per_flip_traffic)            # warmup: trace every bucket/point

        # ---- phase A: repairable view SEUs under live traffic -------------
        for i in range(n_view_flips):
            rec = injector.flip(i)
            key = (rec.region.tensor, rec.region.bits, rec.region.align)
            p0 = passes()
            deadline = time.monotonic() + 15.0
            while packed.verify(bits=None) and time.monotonic() < deadline:
                time.sleep(SCRUB_INTERVAL)
            used = passes() - p0
            repaired = packed.verify() == []
            t = packed.tensors[rec.region.tensor]
            with t._lock:
                buf = np.array(t._packed[(rec.region.bits, rec.region.align)])
            bitexact = bool(np.array_equal(buf, golden_views[key]))
            flips.append({"region": rec.region.label(), "passes": used,
                          "detected": repaired, "bitexact": bitexact})
            serve(per_flip_traffic)        # traffic continues post-repair

        stats_a = router.stats()

        # ---- phase B: unrepairable master-code SEU ------------------------
        # barrier: phase A is fast enough (~100ms of flips) that replica b's
        # crash heal — gated on heal_cooldown_s — may still be pending; wait
        # for the crash chaos to fully resolve so the codes flip hits a fleet
        # of three LIVE pumps and every ejection below names the quarantine
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            reps = router.stats()["replicas"]
            if all(r["state"] == HealthState.HEALTHY.value and r["alive"]
                   for r in reps.values()):
                break
            time.sleep(0.01)
        # drain is done (serve() claims every ticket); flip the int8 master
        BitFlipInjector(packed, seed=2, kinds=("codes",)).flip(99)
        # eject_cause persists across readmission, so "every replica shows a
        # quarantined last-ejection" is race-free to wait on
        deadline = time.monotonic() + 20.0
        quarantined_causes: List[str] = []
        while time.monotonic() < deadline:
            reps = router.stats()["replicas"]
            quarantined_causes = [r["eject_cause"] for r in reps.values()
                                  if r["eject_cause"] is not None]
            if sum(c == "quarantined" for c in quarantined_causes) \
                    == len(reps):
                break
            time.sleep(0.01)
        # heal: the sentinel rebuilds through the factories (which restore
        # the pristine master); wait until the whole fleet is readmitted
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            reps = router.stats()["replicas"]
            if all(r["state"] == HealthState.HEALTHY.value and r["alive"]
                   for r in reps.values()):
                break
            time.sleep(0.01)
        serve(per_flip_traffic)            # post-heal traffic must be clean
        stats = router.stats()
    wall = time.perf_counter() - t0
    for sc in scrubbers:
        sc.stop()

    detected_total = sum(sc.detected_flips for sc in scrubbers)
    repaired_total = sum(sc.repaired_views for sc in scrubbers)
    quarantines_total = sum(sc.quarantines for sc in scrubbers)
    submitted = counters["ok"] + counters["err"]
    return {
        "mode": "integrity_sdc",
        "replicas": len(stats["replicas"]),
        "view_flips": n_view_flips,
        "flips": flips,
        "window_passes": WINDOW_PASSES,
        "scrub_rate_mb_s": SCRUB_RATE / 1e6,
        "detected_flips": detected_total,
        "repaired_views": repaired_total,
        "quarantines": quarantines_total,
        "quarantined_causes": quarantined_causes,
        "quarantine_detail": [
            {"scrubber": sc.tag, "regions": sorted(sc.quarantined),
             "detected": sc.detected_flips, "repaired": sc.repaired_views}
            for sc in scrubbers],
        "canary_failures": stats["canary_failures"],
        "served_ok": counters["ok"],
        "served_err": counters["err"],
        "shed": counters["shed"],
        "corrupted_served": counters["corrupted"],
        "submitted": submitted,
        "availability": round(stats["availability"], 4),
        "availability_phase_a": round(stats_a["availability"], 4),
        "b_generation": stats["replicas"]["b"]["generation"],
        "b_readmissions": stats["replicas"]["b"]["readmissions"],
        "fleet_healthy_final": all(
            r["state"] == HealthState.HEALTHY.value
            for r in stats["replicas"].values()),
        "scrubbed_mb": round(sum(sc.scrubbed_bytes
                                 for sc in scrubbers) / 1e6, 2),
        "probes": stats["probes"],
        "retries": stats["retries"],
        "wall_s": round(wall, 3),
    }


def evaluate(row: Dict) -> Dict:
    detect_ok = (all(f["detected"] and f["passes"] <= row["window_passes"]
                     for f in row["flips"])
                 and row["detected_flips"] >= row["view_flips"] + 1)
    zero_corrupted = row["corrupted_served"] == 0
    repair_ok = (all(f["bitexact"] for f in row["flips"])
                 and row["repaired_views"] >= row["view_flips"])
    # phase B runs against a fully-healed fleet, so EVERY replica's last
    # ejection must name the quarantine (not a coincident pump death)
    quarantine_ok = (row["quarantines"] >= 1
                     and len(row["quarantined_causes"]) == row["replicas"]
                     and all(c == "quarantined"
                             for c in row["quarantined_causes"])
                     and row["fleet_healthy_final"])
    avail_ok = row["availability"] >= 0.99
    crash_ok = (row["b_generation"] >= 2 and row["b_readmissions"] >= 1)
    return {
        "pass": (detect_ok and zero_corrupted and repair_ok
                 and quarantine_ok and avail_ok and crash_ok),
        "detect_ok": detect_ok,
        "zero_corrupted": zero_corrupted,
        "repair_ok": repair_ok,
        "quarantine_ok": quarantine_ok,
        "availability_ok": avail_ok,
        "availability": row["availability"],
        "crash_readmit_ok": crash_ok,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="2 view flips, short traffic")
    ap.add_argument("--out", default="BENCH_integrity.json",
                    help="JSON output path")
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero when an integrity criterion fails")
    args = ap.parse_args()
    row = run(full=not args.quick)
    print("integrity_sdc," + ",".join(
        f"{k}={v}" for k, v in row.items() if k != "flips"))
    crit = evaluate(row)
    print("integrity_sdc,mode=criterion,"
          + ",".join(f"{k}={v}" for k, v in crit.items()))
    doc = {
        "backend": jax.default_backend(),
        "quick": args.quick,
        "row": row,
        "criterion": crit,
    }
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"# wrote {args.out}")
    if args.check and not crit["pass"]:
        raise SystemExit(f"integrity criterion failed: {crit}")


if __name__ == "__main__":
    from repro.caches import enable_compile_cache
    enable_compile_cache()
    main()
