"""The span recorder (repro.spans) and the spans the program records at its
layer boundaries: the served path (submit, queue, the pump's select /
assemble / dispatch / force / demux / nap), set-up (DesignFlow's phases,
an executable's compile) and the kernels' autotune sweeps."""
import sys
import threading
import time
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.configs.mnist_cnn import CONFIG as CNN
from repro.core.flow import DesignFlow
from repro.core.reader import cnn_to_ir
from repro.core.writers.jax_writer import BatchedExecutable
from repro.kernels.qmatmul import ops as qops
from repro.models import cnn
from repro.quant.qtypes import DatatypeConfig
from repro.runtime.serve import PUMP_THREAD, AccelServer

PUMP_SPANS = ("serve.select", "serve.assemble", "serve.dispatch",
              "serve.force", "serve.demux")


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@pytest.fixture
def recorder():
    rec = spans.enable()
    try:
        yield rec
    finally:
        spans.disable()


def _sizes(n):
    return [1 + (i * 5) % 4 for i in range(n)]      # 1..4 rows


def test_recorder_buffer_counters_and_snapshot():
    rec = spans.Recorder(capacity=2)
    rec.add("a", 1, 2, x=1)
    rec.add("b", 2, 3, track="t")
    rec.add("c", 3, 4)
    rec.count("k")
    rec.count("k", 2)
    snap = rec.snapshot()
    assert [s.name for s in snap["spans"]] == ["a", "b"]
    assert snap["spans"][0].ids == {"x": 1}
    assert snap["spans"][0].track == threading.current_thread().name
    assert snap["spans"][1].track == "t"
    assert snap["dropped"] == 1 and snap["counters"] == {"k": 3}
    rec.add("d", 4, 5)
    assert len(snap["spans"]) == 2          # a copy, not a view


def test_enable_disable_active():
    assert spans.active() is None and spans.snapshot() is None
    rec = spans.enable()
    try:
        assert spans.active() is rec
        with spans.span("cold", a=1):
            pass
        ((name, s, e, _, ids),) = spans.snapshot()["spans"]
        assert name == "cold" and s <= e and ids == {"a": 1}
    finally:
        assert spans.disable() is rec
    assert spans.active() is None
    with spans.span("ignored"):
        pass
    assert len(rec.spans) == 1


def test_off_by_default_records_nothing(monkeypatch):
    """(a) A served run, synchronous and in the background, with the
    recorder off: nothing is recorded and no site reads the clock (each
    pays its one None check)."""
    assert spans.active() is None
    reads = []
    real = time.time_ns

    def counted():
        caller = sys._getframe(1).f_globals.get("__name__", "")
        if caller.startswith("repro."):
            reads.append(caller)
        return real()

    monkeypatch.setattr(time, "time_ns", counted)
    exe = BatchedExecutable(lambda x: x * 2)
    srv = AccelServer(exe, max_batch=8, max_wait=0.0005)
    for m in _sizes(6):
        srv(np.ones((m, 3), np.float32))
    with srv:
        tks = [srv.submit(np.ones((m, 3), np.float32)) for m in _sizes(12)]
        for tk in tks:
            tk.result(timeout=30)
    assert srv.executed_batches > 0
    assert spans.active() is None
    assert reads == []


def test_served_spans_per_batch_and_request(recorder):
    """(b) The synchronous pump over N requests: one select / assemble /
    dispatch / force / demux per batch; one serve.submit and serve.queue
    per request, whose batch id names a recorded batch and whose queue span
    ends at or before that batch's dispatch starts."""
    clock = FakeClock()
    srv = AccelServer(lambda x: x + 1, max_batch=8, max_wait=1.0, clock=clock)
    n = 17
    tks = [srv.submit(np.ones((m, 2), np.float32)) for m in _sizes(n)]
    batches = srv.pump(flush=True)
    for tk in tks:
        srv.result(tk)
    got = recorder.snapshot()["spans"]
    per = Counter(s.name for s in got)
    assert per["serve.submit"] == n and per["serve.queue"] == n
    for name in PUMP_SPANS:
        assert per[name] == batches, name
        ids = Counter(s.ids["batch"] for s in got if s.name == name)
        assert set(ids.values()) == {1}
    dispatch = {s.ids["batch"]: s for s in got if s.name == "serve.dispatch"}
    assert len(dispatch) == batches
    assert [r.batch for r in srv.reports] == sorted(dispatch)
    for r in srv.reports:
        d = dispatch[r.batch]
        assert (d.ids["bucket"], d.ids["rows"]) == (r.bucket, r.rows)
    queue = [s for s in got if s.name == "serve.queue"]
    assert {s.ids["rid"] for s in queue} == {tk.rid for tk in tks}
    submit = {s.ids["rid"]: s for s in got if s.name == "serve.submit"}
    for q in queue:
        assert q.track == spans.REQUESTS
        assert q.ids["batch"] in dispatch
        assert q.end_ns <= dispatch[q.ids["batch"]].start_ns
        assert q.start_ns == submit[q.ids["rid"]].start_ns
    # the pump's spans of one batch follow one another
    for bid, d in dispatch.items():
        order = sorted((s for s in got if s.ids.get("batch") == bid
                        and s.name in PUMP_SPANS), key=lambda s: s.start_ns)
        assert [s.name for s in order] == list(PUMP_SPANS)
        assert all(a.end_ns <= b.start_ns for a, b in zip(order, order[1:]))


def test_background_pump_spans_on_the_pump_thread(recorder):
    srv = AccelServer(lambda x: x + 1, max_batch=4, max_wait=0.001)
    with srv:
        tks = [srv.submit(np.ones((1, 2), np.float32)) for _ in range(9)]
        for tk in tks:
            tk.result(timeout=30)
        time.sleep(0.01)                    # the pump naps, queue empty
    got = recorder.snapshot()["spans"]
    tracks = {s.name: s.track for s in got}
    for name in PUMP_SPANS + ("serve.nap",):
        assert tracks[name] == PUMP_THREAD, name
    # the pump's spans tile its time: each starts where the one before ended
    pump = sorted((s for s in got if s.track == PUMP_THREAD),
                  key=lambda s: s.start_ns)
    assert all(a.end_ns == b.start_ns for a, b in zip(pump, pump[1:]))
    assert tracks["serve.submit"] == threading.current_thread().name
    assert sum(s.name == "serve.queue" for s in got) == 9


def test_compile_span_on_a_cache_miss_only(recorder):
    """(c) The first call of a new bucket is one exe.compile span; a repeat
    records none."""
    exe = BatchedExecutable(lambda x: x * 2)
    exe(np.ones((2, 3), np.float32))
    exe(np.ones((2, 3), np.float32))
    exe(np.ones((4, 3), np.float32))
    exe(np.ones((4, 3), np.float32))
    got = [s for s in recorder.snapshot()["spans"] if s.name == "exe.compile"]
    assert [s.ids["bucket"] for s in got] == [2, 4]
    assert exe.misses == 2 and exe.hits == 2


def test_design_flow_phases(recorder):
    """(d) DesignFlow.run records its transform, calibration and one write
    per target."""
    params = cnn.init_params(CNN, jax.random.PRNGKey(0))
    x = jax.random.uniform(jax.random.PRNGKey(1), (2, 28, 28, 1))
    g = cnn_to_ir(CNN, {k: np.asarray(v) for k, v in params.items()})
    DesignFlow(g).run(targets=("jax", "qjax"), dtconfig=DatatypeConfig(8, 8),
                      calib_inputs=(x,))
    got = recorder.snapshot()["spans"]
    names = [s.name for s in got]
    assert names == ["flow.transform", "flow.calibrate", "flow.write",
                     "flow.write"]
    assert [s.ids for s in got[2:]] == [{"target": "jax"},
                                        {"target": "qjax"}]
    assert all(a.end_ns <= b.start_ns for a, b in zip(got, got[1:]))


def test_autotune_sweep_span_and_counter(recorder):
    calls = []

    def make_call(c):
        calls.append(c)
        return lambda x: x * c

    best = qops._fastest({3, 1, 2}, make_call, [jnp.ones((8, 8))])
    assert best in (1, 2, 3) and sorted(calls) == [1, 2, 3]
    snap = recorder.snapshot()
    (sweep,) = snap["spans"]
    assert sweep.name == "kernels.autotune" and sweep.ids == {"candidates": 3}
    assert snap["counters"] == {"kernels.autotune_sweeps": 1}


@pytest.mark.parametrize("capacity", [10**6, 500])
def test_recorder_loses_no_span_across_threads(capacity):
    """Threads adding at once, switching often: every span is either kept
    or counted as dropped, and the buffer overshoots its capacity by at
    most one span per thread (its check and append are not one step)."""
    rec = spans.Recorder(capacity=capacity)
    threads, each = 16, 400
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        workers = [threading.Thread(
            target=lambda k=k: [rec.add("s", i, i + 1, batch=k)
                                for i in range(each)]) for k in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=60)
        assert not any(w.is_alive() for w in workers)
    finally:
        sys.setswitchinterval(old)
    snap = rec.snapshot()
    assert len(snap["spans"]) + snap["dropped"] == threads * each
    assert len(snap["spans"]) <= capacity + threads - 1


def test_recorded_spans_leave_the_collector():
    """A recorded span holds no container: after one collection the garbage
    collector no longer tracks it, so full collections never scan a
    window's spans."""
    import gc
    rec = spans.Recorder()
    for i in range(100):
        rec.add("serve.select", i, i + 1, batch=i, bucket=8, rows=6)
    gc.collect()
    assert not any(gc.is_tracked(s) for s in rec.spans)
    assert rec.snapshot()["spans"][7].ids == {"batch": 7, "bucket": 8,
                                              "rows": 6}
