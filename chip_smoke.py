"""Smoke run of the packed-weight serving path on a TPU chip.

    python chip_smoke.py [--seed 0]          # one chip
    python chip_smoke.py --chips 4           # the data-parallel mesh path

One chip: builds the paper's mnist-cnn (Table II: 28x28x1, conv 16/32, FC 10)
and the depthwise-separable separable-cnn from ``--seed`` with random weights
and D8 activations, so the fully-integer ``int8_act`` path runs; calibrates
on a seeded batch; serves requests of sizes 1, 3, 8 and 11 (11 > max_batch,
so the split path runs), one after another, through
``FlowResult.serve_adaptive`` at W8, W4 and W2 (each pinned with
``FixedSelector``); and checks that the served program holds the compiled
Pallas kernels (``tpu_custom_call``), that no batch failed, and that every
answer agrees with the same graph's reference path and with the float32
model.

``--chips 4``: serves mnist-cnn through ``AccelServer(DistWriter.
build_batched(mesh))`` on a 4-device ``("data",)`` mesh and checks it
against the same graph on one chip, and that a served batch's output is
sharded over all four devices.

Every phase raises on failure; the last line of standard output is
``{"ok": true, "device": {...}}`` only when all of them passed.  Without a
TPU the script exits non-zero and prints no result.  Timings printed along
the way are smoke numbers from one run, not benchmark results.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.caches import enable_compile_cache  # noqa: E402
from repro.configs.mnist_cnn import CONFIG as MNIST  # noqa: E402
from repro.configs.separable_cnn import CONFIG as SEPARABLE  # noqa: E402
from repro.core.adaptive import FixedSelector  # noqa: E402
from repro.core.flow import DEFAULT_POINTS, DesignFlow  # noqa: E402
from repro.core.reader import cnn_to_ir, separable_cnn_to_ir  # noqa: E402
from repro.core.writers.qjax_writer import QJaxWriter  # noqa: E402
from repro.kernels import autotune  # noqa: E402
from repro.kernels.qmatmul.ops import resolve_interpret  # noqa: E402
from repro.models import cnn  # noqa: E402
from repro.quant.ptq import act_code_qtype  # noqa: E402
from repro.quant.qtypes import DatatypeConfig  # noqa: E402
from repro.runtime.serve import AccelServer  # noqa: E402

# model name -> (config, init_params, to_ir, float32 forward -> logits)
MODELS = {
    "mnist-cnn": (MNIST, cnn.init_params, cnn_to_ir,
                  lambda p, x, cfg: cnn.forward(p, x, cfg)[0]),
    "separable-cnn": (SEPARABLE, cnn.init_separable_params,
                      separable_cnn_to_ir, cnn.separable_forward),
}
# the custom calls each model's served program must contain
KERNELS = {"mnist-cnn": ("qgemm_kernel",),
           "separable-cnn": ("qgemm_kernel", "qconv_dw_kernel")}

PRECISION = DatatypeConfig(8, 8)     # D8: int8 activation codes end to end
REQUEST_SIZES = (1, 3, 8, 11)
MAX_BATCH = 8
BUCKETS = (1, 8)
CALIB_ROWS = 16
RESULT_TIMEOUT_S = 600.0

# Kernel vs the same graph's reference path (use_kernel=False), in steps of
# the output's activation code: none.  Both accumulate the same integers
# exactly (int32 on the MXU vs an exact f32 dot/conv) and fold the same
# power-of-two scales.  The one known source of a step, an epilogue's
# ``acc * s + bias`` contracted into one FMA on one side and rounded twice on
# the other (see tests/test_depthwise.py), needs a nonzero bias, and these
# seeded graphs fold identity batch-norm into zero biases.
KERNEL_REF_STEPS = 0
# Top-1 agreement with the float32 model (HIGHEST matmul precision) over the
# 23 requested rows.  The weights are random, so the logits of a row lie close
# together and each coarser weight view flips more of them.  The CPU reference
# path measures, for seed 0: mnist-cnn W8 1.0, W4 1.0, W2 0.957; separable-cnn
# W8 0.957, W4 0.696, W2 0.391.  The floors sit about three rows below that
# and above chance (0.1): they catch a broken path, not a small drift.
TOP1_FLOOR = {"mnist-cnn": {"w8": 0.9, "w4": 0.85, "w2": 0.75},
              "separable-cnn": {"w8": 0.85, "w4": 0.55, "w2": 0.25}}


def log(msg: str) -> None:
    print(msg, flush=True)


def build(name: str, seed: int, **qjax_options):
    """(params, FlowResult) for one model: random weights from ``seed``, D8
    activations calibrated on a seeded batch, the ``qjax`` target."""
    cfg, init, to_ir, _ = MODELS[name]
    params = init(cfg, jax.random.PRNGKey(seed))
    graph = to_ir(cfg, {k: np.asarray(v) for k, v in params.items()})
    calib = np.random.default_rng(seed).random(
        (CALIB_ROWS, *cfg.image_hw, cfg.in_channels), np.float32)
    res = DesignFlow(graph).run(
        targets=("qjax",), dtconfig=PRECISION, calib_inputs=(calib,),
        writer_kwargs={"qjax": qjax_options} if qjax_options else None)
    return params, res


def _output_step(writer: QJaxWriter) -> float:
    """Value of one code step of the graph output (its int8 activation
    code grid)."""
    out = writer.graph.outputs[0]
    return act_code_qtype(writer.dt.act_bits,
                          writer.act_ranges.get(out, 8.0)).scale


def closed_loop(srv: AccelServer, xs):
    """One client: submit each request after the previous one is answered
    (so every size runs as its own batch).  Returns (outputs, seconds per
    request)."""
    outs, lat = [], []
    for x in xs:
        t0 = time.perf_counter()
        outs.append(np.asarray(srv.submit(x).result(timeout=RESULT_TIMEOUT_S)))
        lat.append(time.perf_counter() - t0)
    return outs, lat


def _served_text(srv: AccelServer, point: str, x) -> str:
    """Lowered text of the program the server ran for ``x``'s bucket."""
    exe = srv.point_executables[point].executable_for(x)
    return exe.lower(x).as_text()


def serve_and_compare(name: str, params, res, *, seed: int) -> dict:
    """Serve every working point through ``res.serve_adaptive`` and check the
    answers; returns per-point telemetry.  Raises on any failure.  Where the
    writer compiles its kernels, the served program must hold them."""
    cfg, _, _, forward = MODELS[name]
    writer = res.writers["qjax"]
    rng = np.random.default_rng(seed + 1)
    shape = (*cfg.image_hw, cfg.in_channels)
    xs = [rng.random((n, *shape), np.float32) for n in REQUEST_SIZES]
    x_all = np.concatenate(xs)
    with jax.default_matmul_precision("highest"):
        y_f32 = np.asarray(jax.jit(lambda x: forward(params, x, cfg))(x_all))
    ref = QJaxWriter(res.graph, writer.dt, res.act_ranges, use_kernel=False)
    step = _output_step(writer)
    compiled_kernels = not resolve_interpret(writer.interpret)

    srv = res.serve_adaptive(DEFAULT_POINTS, selector=FixedSelector(
        DEFAULT_POINTS[0]), max_batch=MAX_BATCH, buckets=BUCKETS,
        max_wait=0.0)
    report = {}
    srv.start()
    try:
        for pt in DEFAULT_POINTS:
            srv.set_selector(FixedSelector(pt))
            passes = []
            for _ in range(2):          # cold (traces + compiles), then warm
                t0 = time.perf_counter()
                outs, lat = closed_loop(srv, xs)
                passes.append((time.perf_counter() - t0, outs, lat))
            (cold_s, y_cold, _), (warm_s, y_warm, lat) = passes
            y = np.concatenate(y_warm)
            assert y.shape == y_f32.shape, (y.shape, y_f32.shape)
            np.testing.assert_array_equal(np.concatenate(y_cold), y)
            codes = y / step
            assert np.array_equal(codes, np.round(codes)), \
                f"{name} {pt.name}: output off its code grid"
            y_ref = np.asarray(jax.jit(ref.build(bits=pt.weight_bits))(x_all))
            diff = np.abs(y - y_ref) / step
            assert diff.max() <= KERNEL_REF_STEPS, (
                f"{name} {pt.name}: kernel vs reference off by "
                f"{diff.max()} code steps (limit {KERNEL_REF_STEPS})")
            top1 = float(np.mean(np.argmax(y, -1) == np.argmax(y_f32, -1)))
            floor = TOP1_FLOOR[name][pt.name]
            assert top1 >= floor, (f"{name} {pt.name}: top-1 agreement with "
                                   f"float32 {top1} < {floor}")
            if compiled_kernels:
                text = _served_text(srv, pt.name, xs[2])
                for kernel in KERNELS[name]:
                    assert "tpu_custom_call" in text and kernel in text, \
                        f"{name} {pt.name}: {kernel} not in the served program"
            report[pt.name] = {
                "cold_pass_s": cold_s, "warm_pass_s": warm_s,
                "warm_request_s": lat,
                "ref_max_steps": float(diff.max()),
                "ref_exact_frac": float(np.mean(diff == 0)),
                "top1_vs_f32": top1}
    finally:
        srv.stop(timeout=RESULT_TIMEOUT_S)
    stats = srv.stats()
    assert stats["pump_errors"] == 0, f"{name}: {stats['pump_errors']} errors"
    assert stats["numerical_faults"] == 0, stats["numerical_faults"]
    assert set(stats["bits_views"]) == {8, 4, 2}, stats["bits_views"]
    report["bits_views"] = stats["bits_views"]
    report["executed_batches"] = stats["executed_batches"]
    return report


def one_chip(seed: int) -> None:
    for name in MODELS:
        t0 = time.perf_counter()
        params, res = build(name, seed)
        writer = res.writers["qjax"]
        assert writer.qpath == "pallas", writer.qpath
        assert not resolve_interpret(writer.interpret)
        assert writer.int8_act_on and writer.packed_storage
        tuned = len(autotune.disk_cache())
        report = serve_and_compare(name, params, res, seed=seed)
        log(f"{name}: build+serve {time.perf_counter() - t0:.1f}s, "
            f"autotune entries +{len(autotune.disk_cache()) - tuned}, "
            f"bits_views {report.pop('bits_views')}, "
            f"batches {report.pop('executed_batches')}, pump_errors 0")
        for point, r in report.items():
            lat = ", ".join(f"{s * 1e3:.2f}" for s in r["warm_request_s"])
            log(f"  {point}: kernel vs ref max {r['ref_max_steps']:g} steps "
                f"(exact {r['ref_exact_frac']:.4f}), top-1 vs f32 "
                f"{r['top1_vs_f32']:.3f}, cold pass {r['cold_pass_s']:.2f}s, "
                f"warm pass {r['warm_pass_s']:.4f}s, smoke request latency "
                f"ms [{lat}] (sizes {list(REQUEST_SIZES)})")


def four_chips(seed: int) -> None:
    """DistWriter's data-parallel mesh path vs the same graph on one chip."""
    from jax.sharding import AxisType
    assert len(jax.devices()) == 4, jax.devices()
    # full-precision convs on both sides, so the only difference left is the
    # per-device batch changing XLA's accumulation order
    jax.config.update("jax_default_matmul_precision", "highest")
    mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
    cfg, init, to_ir, _ = MODELS["mnist-cnn"]
    params = init(cfg, jax.random.PRNGKey(seed))
    graph = to_ir(cfg, {k: np.asarray(v) for k, v in params.items()})
    writer = DesignFlow(graph).run(targets=("dist",)).writers["dist"]
    single = writer.build_batched()
    sharded = writer.build_batched(mesh)
    shardings = []

    def served(*cols):
        out = sharded(*cols)
        shardings.append((cols[0].shape[0], out.sharding))
        return out

    rng = np.random.default_rng(seed + 1)
    xs = [rng.random((n, *cfg.image_hw, cfg.in_channels), np.float32)
          for n in REQUEST_SIZES]
    srv = AccelServer(served, max_batch=MAX_BATCH, buckets=BUCKETS,
                      max_wait=0.0)
    t0 = time.perf_counter()
    with srv:
        outs, _ = closed_loop(srv, xs)
    serve_s = time.perf_counter() - t0
    assert srv.stats()["pump_errors"] == 0
    worst = 0.0
    for x, y in zip(xs, outs):
        y1 = np.asarray(single(x))
        assert y.shape == y1.shape == (x.shape[0], cfg.n_classes)
        worst = max(worst, float(np.max(np.abs(y - y1))
                                 / (np.max(np.abs(y1)) + 1e-9)))
    assert worst <= 1e-4, f"mesh vs one chip: relative difference {worst}"
    full = [s for b, s in shardings if b % 4 == 0]
    assert full, "no served batch divided the mesh"
    assert all(len(s.device_set) == 4 for s in full), full
    log(f"mnist-cnn on a 4-device data mesh: {len(shardings)} batches in "
        f"{serve_s:.1f}s, max relative difference vs one chip {worst:.3g}, "
        f"output sharding {full[0]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()      # before the first compile
    hits = {"hits": 0, "misses": 0}

    def count(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            hits["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            hits["misses"] += 1

    jax.monitoring.register_event_listener(count)
    log(f"device {dev.device_kind} x{len(jax.devices())}, jax "
        f"{jax.__version__}, compile cache {cache_dir}, autotune cache "
        f"{autotune.autotune_cache_path()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    log(f"total {time.perf_counter() - t0:.1f}s, persistent compile cache "
        f"hits {hits['hits']} misses {hits['misses']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
