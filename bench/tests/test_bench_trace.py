"""The trace reduction of bench/trace.py: busy union, idle share, per-kernel
sums and idle-gap labels, on hand-made events and on a trace recorded on
one v5e (``data/mnist-cnn.sat.xplane.pb``: 0.3 s of the mnist-cnn.sat cell,
recorded with the host tracer on, so it also holds the benchmark's spans);
and the clock that places the benchmark's own host spans on the trace."""
import glob
import gzip
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
import trace  # noqa: E402
import work  # noqa: E402

DATA = BENCH / "tests" / "data"
PEAKS = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}


def test_union_and_gaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.gaps([(0, 3), (5, 8)], (0, 10)) == [(3, 5), (8, 10)]
    assert trace.gaps([(2, 4)], (0, 4)) == [(0, 2)]


def _kernel_call(name):
    return (work.KernelCall("qgemm_kernel", 2 * 10**6, 1000)
            if name.startswith("%k") else None)


def test_reduce_hand_made():
    ev = trace.TraceEvents(
        device_ops={"/device:TPU:0": [
            ("%k.1 = s8[2]", 100, 300),      # kernel, inside
            ("%f.2 = s8[2]", 250, 400),      # overlaps the kernel
            ("%k.1 = s8[2]", 600, 700),      # kernel, inside
            ("%f.3 = s8[2]", 950, 1100),     # crosses the window's end
        ]}, origin_ns=0)
    spans = [("bench.submit", 420, 500), ("bench.execute", 400, 600),
             ("bench.sleep", 0, 1000)]
    red = trace.reduce(ev, _kernel_call, PEAKS, (0, 1000), spans)
    assert red["window_s"] == pytest.approx(1000e-9)
    # busy: [100, 400] + [600, 700] + [950, 1000]
    assert red["busy_s"] == pytest.approx(450e-9)
    assert red["kernel_s"] == {"qgemm_kernel": pytest.approx(300e-9)}
    assert red["kernel_events"] == {"qgemm_kernel": 2}
    least = 2 * work.least_seconds(_kernel_call("%k"), PEAKS)
    assert red["kernel_least_s"] == {"qgemm_kernel": pytest.approx(least)}
    assert red["device_ops"][0] == ["%k.1", pytest.approx(300e-9)]
    # gaps [400, 600] (mid 500: bench.execute is the innermost open span),
    # [0, 100] and [700, 950] (only the sleep is open)
    assert [g[0] for g in red["idle_gaps"]] == ["bench.sleep", "bench.execute",
                                                "bench.sleep"]
    assert [g[1] for g in red["idle_gaps"]] == pytest.approx(
        [250e-9, 200e-9, 100e-9])


def test_reduce_needs_one_window():
    ev = trace.TraceEvents(device_ops={"/device:TPU:0": [("%a", 0, 1)]},
                           origin_ns=0)
    with pytest.raises(ValueError):
        trace.reduce(ev, _kernel_call, PEAKS, (5, 5))
    with pytest.raises(ValueError):
        trace.reduce(trace.TraceEvents(origin_ns=0), _kernel_call, PEAKS,
                     (0, 5))


def _host_events(path, prefix):
    """Host events named ``prefix...`` in an ``.xplane.pb``, on its clock."""
    from jax.profiler import ProfileData
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(prefix)]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The recorded trace's device operations; its traced window and host
    spans, as ``bench.*`` host events."""
    path = tmp_path_factory.mktemp("trace") / "mnist-cnn.sat.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / "mnist-cnn.sat.xplane.pb.gz").read_bytes()))
    spans = _host_events(path, "bench.")
    (window,) = [(s, e) for n, s, e in spans if n == "bench.window"]
    return (trace.read_events(str(path)), window,
            [x for x in spans if x[0] != "bench.window"])


def test_recorded_trace_origin(recorded):
    events, _, _ = recorded
    # the profile's start on the wall clock (ns since the epoch)
    assert events.origin_ns == 1792294152555794567
    assert events.since_origin(events.origin_ns + 7) == 7


def _mnist_calls(events):
    """Every Pallas call in mnist-cnn's program is a qgemm_kernel call."""
    sigs = {}
    for ops in events.device_ops.values():
        for name, _, _ in ops:
            shapes = work.event_shapes(name)
            if shapes is not None:
                sigs[work.signature(*shapes)] = "qgemm_kernel"
    return lambda name: work.event_call(name, sigs)


def test_recorded_trace(recorded):
    events, (lo, hi), spans = recorded
    red = trace.reduce(events, _mnist_calls(events), PEAKS, (lo, hi), spans)
    assert red["window_s"] == pytest.approx(0.3, rel=0.01)
    ops = [(n, s, e) for n, s, e in events.device_ops["/device:TPU:0"]
           if s < hi and e > lo]
    # busy union, counted a second way: 1 us bins over the window
    bins = np.zeros(hi - lo + 1000, bool)
    for _, s, e in ops:
        bins[max(s, lo) - lo:min(e, hi) - lo] = True
    assert red["busy_s"] == pytest.approx(bins.sum() / 1e9, rel=1e-6)
    assert 0 < red["busy_s"] < red["window_s"]
    idle = trace.gaps(trace.union([(max(s, lo), min(e, hi)) for _, s, e in ops]),
                      (lo, hi))
    assert sum(e - s for s, e in idle) / 1e9 == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)
    assert sorted((e - s) / 1e9 for s, e in idle)[-1] == pytest.approx(
        red["idle_gaps"][0][1])
    # per-kernel sums: every whole custom call inside the window
    calls = [(n, s, e) for n, s, e in ops
             if "tpu_custom_call" in n and s >= lo and e <= hi]
    assert red["kernel_events"] == {"qgemm_kernel": len(calls)}
    assert red["kernel_s"]["qgemm_kernel"] == pytest.approx(
        sum(e - s for _, s, e in calls) / 1e9)
    assert len(calls) >= 300
    assert 0 < red["kernel_least_s"]["qgemm_kernel"] < red["kernel_s"][
        "qgemm_kernel"]
    labels = {g[0] for g in red["idle_gaps"]}
    assert labels <= {"bench.execute", "bench.submit", "bench.collect",
                      "idle host"}


def test_span_log_meets_the_trace_clock(tmp_path):
    """A span the benchmark keeps on the wall clock lands on the trace's
    clock where the profiler put the same span (CPU profile, host tracer
    on for the comparison's sake)."""
    import jax
    log = run.SpanLog()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        time.sleep(0.02)
        with jax.profiler.TraceAnnotation("bench.mark"), log("bench.mark"):
            time.sleep(0.03)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = trace.read_events(path)
    ((_, s, e),) = _host_events(path, "bench.mark")
    ((_, a, b),) = log.spans
    assert abs(events.since_origin(a) - s) < 1e6       # within 1 ms
    assert abs(events.since_origin(b) - e) < 1e6


class _Part:
    t0, t1 = 4.0, 6.0


def test_rates_over_the_window_and_its_traced_part():
    rec = {"t_open": 0.0,
           "done_t": np.array([1.0, 4.5, 5.5, 9.0, 10.5]),
           "done_images": np.array([2, 8, 8, 4, 1])}
    got = run._rates(rec, _Part, 10.0)
    # 2 + 8 + 8 + 4 images inside the 10 s window; 16 in the traced 2 s
    assert got["window_images_per_s"] == pytest.approx(22 / 10.0)
    assert got["traced_images_per_s"] == pytest.approx(16 / 2.0)
