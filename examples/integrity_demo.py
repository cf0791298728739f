"""Weight-memory integrity demo: SDC detection, scrubbing, self-healing.

    PYTHONPATH=src python examples/integrity_demo.py
    PYTHONPATH=src python examples/integrity_demo.py --soak --seconds 8 --seed 7

One shared :class:`PackedWeights` buffer backs every W8/W4/W2 working point
on every replica — which makes it the fleet's single point of *silent*
failure: a bit flip there corrupts all replicas at once while availability
stays at 100%.  This demo walks the defenses end-to-end:

1. every region (int8 master codes, f32 channel scales, each cached W4/W2
   packed view) is CRC-sealed at pack time; a rate-bounded
   :class:`Scrubber` per replica re-hashes them round-robin;
2. a flipped W4/W2 **view** is repaired in place — re-derived bit-exactly
   from the intact master codes, no restart, no reload;
3. a flipped **master code** is unrepairable: the scrubber quarantines it,
   the server dies with a typed :class:`IntegrityError` (zero
   post-detection corrupted results), the sentinel ejects the replica with
   a ``quarantined`` cause, and the factory heals it with a pristine
   master before readmission;
4. semantic :class:`CanarySet` probes ride the sentinel's real
   submit/result path, catching corruption checksums cannot see.

``--soak`` runs a seeded, time-bounded bit-flip soak instead (the CI smoke
mode): continuous view-region SEUs plus one mid-run master-code SEU, under
live traffic.  It exits non-zero if ANY served result is corrupted (checked
against golden outputs), any ticket is lost, or the fleet/buffer fails to
end clean.
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.mnist_cnn import CONFIG as CNN
from repro.core.adaptive import WorkingPoint, shared_point_executables
from repro.core.flow import DesignFlow
from repro.core.reader import cnn_to_ir
from repro.models import cnn
from repro.quant.qtypes import DatatypeConfig
from repro.runtime.fleet import FleetRouter, HealthState
from repro.runtime.integrity import BitFlipInjector, CanarySet, Scrubber
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
    pts = shared_point_executables(res.writers["qjax"], POINTS)
    packed = pts["w8"].packed
    for t in packed.tensors.values():    # derive the W4/W2 view regions
        t.packed_view(4)
        t.packed_view(2)
    return pts, packed, pool


def goldens(packed, pts, pool):
    master = {n: (np.array(t.codes), np.array(t.scale))
              for n, t in packed.tensors.items()}
    outputs = {name: {s: np.asarray(exe(pool[:s])) for s in (1, 2, 4)}
               for name, exe in pts.items()}
    return master, outputs


def restore_master(packed, master):
    """Heal-path restore: pristine codes/scales, views re-derived."""
    for n, t in packed.tensors.items():
        codes, scale = master[n]
        t.codes = jnp.asarray(codes)
        t.scale = jnp.asarray(scale)
        t.seal()
        for (bits, align) in list(t._packed):
            t.repair_view(bits, align=align)


def fleet(pts, packed, master, pool, scrubbers, *, seed=0):
    def make_factory(name):
        def factory():
            if packed.verify():          # healing a quarantined buffer
                restore_master(packed, master)
            srv = AccelServer(pts["w8"], max_batch=MAX_BATCH, max_wait=0.002,
                              point_executables=dict(pts), pipeline_depth=2)
            old = scrubbers.pop(name, None)
            if old is not None:
                old.stop()
            sc = Scrubber(packed, rate_bytes_s=20e6, interval_s=0.002)
            srv.attach_scrubber(sc)      # quarantine -> fatal IntegrityError
            sc.start()
            scrubbers[name] = sc
            return srv
        return factory

    canaries = CanarySet.capture(pts, [(pool[:1],)], k=1,
                                 rtol=1e-3, atol=1e-4)
    return FleetRouter({n: make_factory(n) for n in ("a", "b", "c")},
                       canaries=canaries, retries=3, backoff_s=0.005,
                       probe_interval_s=0.02, heal_cooldown_s=0.2,
                       default_deadline_s=60.0, seed=seed)


def wait_for(cond, seconds, poll=0.01):
    deadline = time.monotonic() + seconds
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(poll)
    return cond()


def all_healthy(router):
    return all(r["state"] == HealthState.HEALTHY.value and r["alive"]
               for r in router.stats()["replicas"].values())


def print_integrity(stats):
    it = stats["integrity"]
    print(f"  integrity: scrubbed={it['scrubbed_bytes'] / 1e6:.1f}MB "
          f"passes={it['scrub_passes']} detected={it['detected_flips']} "
          f"repaired={it['repaired_views']} "
          f"quarantines={it['quarantines']} "
          f"canary_failures={stats['canary_failures']}")
    for name, rep in stats["replicas"].items():
        print(f"  replica {name}: state={rep['state']} "
              f"eject_cause={rep['eject_cause']} gen={rep['generation']} "
              f"readmissions={rep['readmissions']}")


def demo(args):
    pts, packed, pool = build_points()
    master, _ = goldens(packed, pts, pool)
    scrubbers = {}
    router = fleet(pts, packed, master, pool, scrubbers)
    regions = packed.regions()
    print(f"== packed buffer: {len(regions)} CRC-sealed regions, "
          f"{sum(r.nbytes for r in regions)} bytes/scrub period ==")
    try:
        with router:
            router(pool[:2])

            print("== SEU 1: flip a bit in a W4 packed view ==")
            v4 = next(r for r in regions if r.kind == "view" and r.bits == 4)
            BitFlipInjector(packed, seed=args.seed).flip(region=v4)
            assert wait_for(lambda: packed.verify() == [], 10.0, poll=0.002)
            print(f"  {v4.label()}: detected and repaired in place from the "
                  "master codes (bit-exact, no restart)")
            router(pool[:2])

            print("== SEU 2: flip a bit in the int8 master codes ==")
            codes = next(r for r in regions if r.kind == "codes")
            BitFlipInjector(packed, seed=args.seed + 1).flip(region=codes)
            ejected = wait_for(
                lambda: all(r["eject_cause"] == "quarantined"
                            for r in router.stats()["replicas"].values()),
                20.0)
            print(f"  {codes.label()}: unrepairable -> every replica died "
                  f"typed + ejected 'quarantined' ({ejected})")
            healed = wait_for(lambda: all_healthy(router), 30.0)
            print(f"  factories restored the pristine master -> fleet "
                  f"healed and readmitted ({healed})")
            router(pool[:2])
            print("== final fleet state ==")
            print_integrity(router.stats())
    finally:
        for sc in scrubbers.values():
            sc.stop()


def soak(args):
    pts, packed, pool = build_points()
    master, golden_out = goldens(packed, pts, pool)
    scrubbers = {}
    router = fleet(pts, packed, master, pool, scrubbers, seed=args.seed)
    view_seu = BitFlipInjector(packed, rate=args.flip_rate, seed=args.seed,
                               kinds=("view",))
    codes_seu = BitFlipInjector(packed, seed=args.seed + 1,
                                kinds=("codes",))
    rng = np.random.default_rng(args.seed)
    t_end = time.monotonic() + args.seconds
    codes_at = time.monotonic() + args.seconds / 2
    submitted = ok = err = corrupted = shed = step = 0
    print(f"== seeded bit-flip soak: {args.seconds}s, view flip_rate="
          f"{args.flip_rate}/round + 1 master-code SEU, seed={args.seed} ==")
    try:
        with router:
            router(pool[:1])              # warm the trace caches
            while time.monotonic() < t_end:
                step += 1
                view_seu.maybe_flip(step)
                if codes_seu.injected_flips == 0 \
                        and time.monotonic() >= codes_at:
                    codes_seu.flip(step)
                sizes = [int(s) for s in rng.choice([1, 2, 4], size=6)]
                tickets = []
                for s in sizes:
                    try:
                        tickets.append((s, router.submit(pool[:s])))
                    except Exception:
                        # the master SEU quarantines EVERY replica at once:
                        # the fleet sheds (fail-stop) while the sentinel
                        # heals — shed is not lost and never corrupted
                        shed += 1
                        time.sleep(0.05)
                submitted += len(tickets)
                for s, t in tickets:
                    try:
                        val = t.result(timeout=120)
                    except Exception:
                        err += 1          # typed failure: never corrupted
                        continue
                    ok += 1
                    out = np.asarray(val[0] if isinstance(val, tuple)
                                     else val)
                    if not any(np.allclose(out, g[s], rtol=1e-4, atol=1e-5)
                               for g in golden_out.values()):
                        corrupted += 1
            fleet_clean = wait_for(lambda: all_healthy(router), 30.0)
            buffer_clean = wait_for(lambda: packed.verify() == [], 10.0)
            stats = router.stats()
    finally:
        for sc in scrubbers.values():
            sc.stop()
    lost = submitted - ok - err
    print(f"== soak done: submitted={submitted} ok={ok} "
          f"typed_failures={err} shed={shed} lost={lost} "
          f"corrupted_served={corrupted} "
          f"view_flips={view_seu.injected_flips} "
          f"codes_flips={codes_seu.injected_flips} ==")
    print_integrity(stats)
    if corrupted:
        raise SystemExit(f"soak served {corrupted} corrupted results")
    if lost:
        raise SystemExit(f"soak lost {lost} tickets")
    if not (fleet_clean and buffer_clean):
        raise SystemExit("soak did not end with a healthy fleet and a "
                         "clean buffer")
    print("zero corrupted results, zero lost tickets, fleet healed clean")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--soak", action="store_true",
                    help="seeded time-bounded bit-flip soak (CI smoke mode)")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--flip-rate", type=float, default=0.2,
                    help="per-round probability of a view-region SEU")
    args = ap.parse_args()
    if args.soak:
        soak(args)
    else:
        demo(args)


if __name__ == "__main__":
    from repro.caches import enable_compile_cache
    enable_compile_cache()
    main()
