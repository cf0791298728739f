"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``).  The run:

1. set-up, timed from the start of this script as ``setup_s``: weights from
   the seed (one jitted call, on the device), ``DesignFlow`` to the ``qjax``
   target calibrated on 16 seeded rows, the ``AccelServer`` from
   ``serve_adaptive`` pinned to the mix's working point, every bucket's
   program called once, one request of each size sent through the server;
2. the window: ``--seconds`` of the mix's load, open or closed loop,
   driving ``AccelServer.submit`` and timing every request on the client
   side (open loop: from when it was due);
3. after the window: every request still out is awaited, the device's peak
   memory is read, the server is stopped, and a seeded sample of the
   finished requests is compared with the reference at the configuration's
   precision (:mod:`check`).

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, from a run whose window keeps the benchmark's host spans
(``bench.*``) and runs under the profiler, on the device alone.  The
profiler starts before the window opens and stops after it closes, so
neither costs the window anything.  The trace's numbers cover the middle
``TRACE_SECONDS`` of the window; the server's counters, the generator's lag
and ``mfu_int8``'s rate cover the whole window.  Each metric is read
by ``bench/metrics/<name>.py`` from the run's record; a metric split by the
end-to-end metric it moves (``batch_rows.open``, ``batch_rows.sat``) may
share ``bench/metrics/batch_rows.py``.  The last line of standard output is
one JSON object; the compared numbers and their limits are the last lines
of standard error.  A run is correct when every request of the window was
answered (``failed_requests`` 0) and the sampled answers agree with the
reference (:mod:`check`).  With no TPU, or fewer chips than the cell asks
for, the run exits with code 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()       # set-up is timed from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import queue  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from collections import deque  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import traffic  # noqa: E402

RESULT_WAIT_S = 60.0     # how long past the window's close an answer may take
KERNELS = ("qgemm_kernel", "qconv_dw_kernel")
TRACE_SECONDS = 2.0      # the traced part of a --trace 1 window, mid-window
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


# -- the benchmark's own files, found by name --------------------------------

def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell_spec(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return load_json(BENCH / "configs" / f"{name}.json")


def metrics_for(bench: dict, cell: str, kind: str) -> list:
    """The ``kind`` (``end_to_end`` / ``per_layer``) metrics ``cell``
    reports: those that list it, and those that list no cells."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def reader(name: str):
    """``read(record)`` of ``bench/metrics/<name>.py``, else of the reader
    of the name without its last ``.`` part (``batch_rows.open`` ->
    ``batch_rows.py``)."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.exists() and "." in name:
        path = BENCH / "metrics" / f"{name.rsplit('.', 1)[0]}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json; "
                       f"it has {sorted(table)}")
    return table[kind]


# -- host spans, the executable the server is handed, compile counts --------

class _NoSpan:
    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


class SpanLog:
    """The benchmark's host spans in a traced run, kept by the benchmark as
    (name, start_ns, end_ns) on the wall clock (``time.time_ns``), which the
    trace's clock counts from (:mod:`trace`).  ``span(name)`` opens one."""

    def __init__(self):
        self.spans = []

    @contextlib.contextmanager
    def __call__(self, name):
        t = time.time_ns()
        try:
            yield
        finally:
            self.spans.append((name, t, time.time_ns()))


class Spanned:
    """The point executable handed to the server in a traced run: each call
    runs inside a ``bench.execute`` span.  Everything else is the wrapped
    executable's."""

    def __init__(self, exe, span):
        self._exe = exe
        self._span = span

    def __call__(self, *cols):
        with self._span("bench.execute"):
            return self._exe(*cols)

    def __getattr__(self, name):
        return getattr(self._exe, name)


class CompileCounter:
    """Lowerings and backend compiles seen by JAX, via ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.count += 1


class GcPauses:
    """Collections of Python's cyclic garbage collector, as (start, seconds):
    each holds the interpreter lock, so every thread of the run waits."""

    def __init__(self):
        self.pauses = []
        self._t = None
        gc.callbacks.append(self._seen)

    def _seen(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((self._t, time.perf_counter() - self._t))

    def close(self):
        gc.callbacks.remove(self._seen)


def start_profiler(log_dir: str) -> None:
    """The profiler on the device alone.  The host tracer stays off: at
    level 1 it records PJRT's events, a transpose per chunk of every input
    copied to the chip, which slowed a MobileNet run about 9 times."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)


class TracedPart(threading.Thread):
    """Marks the part of the window the trace is reduced over: ``seconds``
    of it, starting ``delay`` seconds in; from ``t0`` to ``t1`` on the
    host's ``perf_counter``, ``w0`` to ``w1`` on its wall clock."""

    def __init__(self, delay: float, seconds: float):
        super().__init__(name="bench-traced-part")
        self.delay, self.seconds = delay, seconds
        self.t0 = self.t1 = self.w0 = self.w1 = None

    def run(self):
        time.sleep(self.delay)
        self.t0, self.w0 = time.perf_counter(), time.time_ns()
        time.sleep(self.seconds)
        self.t1, self.w1 = time.perf_counter(), time.time_ns()


# -- the window ---------------------------------------------------------------

def _claim(srv, tk, deadline):
    """Wait for one ticket; ``(answer or None, time it was seen)``."""
    if not tk.wait(max(0.0, deadline - time.perf_counter())):
        srv.drop(tk)
        return None, time.perf_counter()
    t = time.perf_counter()
    try:
        return np.asarray(srv.result(tk)), t
    except Exception:       # noqa: BLE001 — a failed request counts in failed
        return None, t


def drive_open(srv, mix, sched, pool, seconds, sample, span):
    """Open loop: request k is sent at ``due_s[k]`` after the window opens,
    whatever the server does; a collector thread takes the answers in order
    (the server answers one tenant's requests in order).  Latency is from
    when the request was due."""
    n = len(sched.sizes)
    done_t = np.full(n, np.nan)
    lag = np.zeros(n)
    refused = []
    lost = []
    q: queue.Queue = queue.Queue()
    t0 = time.perf_counter()
    due = t0 + sched.due_s

    def collect():
        while (item := q.get()) is not None:
            k, tk = item
            y, t = _claim(srv, tk, t0 + seconds + RESULT_WAIT_S)
            with span("bench.collect"):
                if y is None:
                    lost.append(k)
                    continue
                done_t[k] = t
                sample.offer((int(sched.offsets[k]), int(sched.sizes[k]), y))

    th = threading.Thread(target=collect, name="bench-collector")
    th.start()
    try:
        for k in range(n):
            wait = due[k] - time.perf_counter()
            if wait > 0:
                with span("bench.sleep"):
                    time.sleep(wait)
            lag[k] = time.perf_counter() - due[k]
            o, m = int(sched.offsets[k]), int(sched.sizes[k])
            try:
                with span("bench.submit"):
                    tk = srv.submit(pool[o:o + m])
            except Exception:   # noqa: BLE001 — QueueFull, a dead pump
                refused.append(k)
                continue
            q.put((k, tk))
    finally:
        q.put(None)
        th.join()
    ok = ~np.isnan(done_t)
    return {"attempted": n, "failed": len(refused) + len(lost),
            "t_open": t0, "latency_s": (done_t - due)[ok], "gen_lag_s": lag,
            "due_t": due, "done_t": done_t[ok], "done_images": sched.sizes[ok],
            "images_done": int(sched.sizes[ok].sum())}


def drive_closed(srv, mix, sched, pool, seconds, sample, span):
    """Closed loop of ``clients`` callers on one thread: ``clients``
    requests are always out; when the oldest is answered the next is sent.
    Throughput counts the images answered inside the window."""
    out: deque = deque()
    n_sched = len(sched.sizes)
    attempted = failed = k = 0
    done_t, done_images = [], []
    t_open = time.perf_counter()
    t_end = t_open + seconds
    while True:
        while len(out) < mix["clients"] and time.perf_counter() < t_end:
            j = k % n_sched
            o, m = int(sched.offsets[j]), int(sched.sizes[j])
            k += 1
            attempted += 1
            try:
                with span("bench.submit"):
                    out.append((j, srv.submit(pool[o:o + m])))
            except Exception:   # noqa: BLE001 — QueueFull, a dead pump
                failed += 1
        if not out:
            break
        j, tk = out.popleft()
        y, t = _claim(srv, tk, t_end + RESULT_WAIT_S)
        with span("bench.collect"):
            if y is None:
                failed += 1
                continue
            done_t.append(t)
            done_images.append(int(sched.sizes[j]))
            sample.offer((int(sched.offsets[j]), int(sched.sizes[j]), y))
    done_t, done_images = np.asarray(done_t), np.asarray(done_images)
    return {"attempted": attempted, "failed": failed, "t_open": t_open,
            "latency_s": np.zeros(0), "gen_lag_s": np.zeros(0),
            "due_t": np.zeros(0), "done_t": done_t, "done_images": done_images,
            "images_done": int(done_images[done_t < t_end].sum())}


class _Sample:
    """The seeded sample of finished requests, plus the first finished
    request of the largest size (the check covers the longest requests)."""

    def __init__(self, k: int, seed: int, longest: int):
        self.reservoir = traffic.Reservoir(k, seed)
        self.longest_size = longest
        self.longest = None

    def offer(self, item):
        if item[1] == self.longest_size and self.longest is None:
            self.longest = item
        self.reservoir.offer(item)

    def items(self) -> list:
        items = list(self.reservoir.items)
        if self.longest is not None and all(i is not self.longest
                                            for i in items):
            items.append(self.longest)
        return items


# -- one run ------------------------------------------------------------------

def _counters(srv) -> dict:
    s = srv.stats()
    return {k: s.get(k, 0) for k in ("scheduled_rows", "padded_rows",
                                     "scheduled_batches", "misses")}


def _rates(rec: dict, part: TracedPart, seconds: float) -> dict:
    """Images answered per second over the whole window, and inside the
    part of it the trace is reduced over."""
    done_t, images = rec["done_t"], rec["done_images"]
    t_open = rec["t_open"]
    whole = (done_t >= t_open) & (done_t < t_open + seconds)
    inside = (done_t >= part.t0) & (done_t < part.t1)
    return {"window_images_per_s": float(images[whole].sum()) / seconds,
            "traced_images_per_s": float(images[inside].sum())
            / (part.t1 - part.t0)}


def run_cell(cell: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, point: str | None = None,
             rate: float | None = None, wrap=None, keep: dict | None = None,
             trace_dir: str | None = None,
             t_start: float | None = None) -> dict:
    """One run of ``cell``; returns the result object (see the module
    docstring).  ``point`` and ``rate`` override the mix's working point and
    open-loop rate: the control and the knee sweep use them, the benchmark's
    own runs never do.  ``require_tpu=False`` lets the tests drive a run on
    the CPU, and ``wrap`` lets them break the served executable underneath
    (``wrap(executable) -> executable``).  ``keep``, a dict, receives the
    run's record (what the metric readers read).  A traced run's profile is
    deleted once read, unless ``trace_dir`` names where to keep it."""
    t_start = T_START if t_start is None else t_start
    bench = benchmark()
    spec = cell_spec(bench, cell)
    cfg = config(spec["config"])
    mix = dict(traffic.load(spec["traffic"]))
    if rate is not None:
        mix.update(knee_per_s=rate, share_of_knee=1.0)
    point = point or mix["point"]

    import jax
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu" or len(devices) < spec["chips"]):
        raise NoChip(f"cell {cell} needs {spec['chips']} TPU chip(s); JAX "
                     f"found {len(devices)} {dev.platform} device(s)")
    import program
    from check import check
    if require_tpu:     # the tests' CPU runs leave JAX's global cache alone
        program.enable_compile_cache()
    compiles = CompileCounter()
    span = SpanLog() if trace else _NoSpan
    peaks = peaks_for(dev.device_kind) if trace else None

    shape = program.image_shape(cfg)
    params = program.make_weights(cfg, seed)
    calib = traffic.calibration_rows(shape, seed)
    result = program.build(cfg, params, calib)
    srv = program.serve(result, point, mix["server"])
    exe = srv.point_executables[point]
    buckets = sorted(set(mix["server"]["buckets"])
                     | {mix["server"]["max_batch"]})
    kernels_by_sig = {}
    for b in buckets:                   # every bucket's program, compiled
        x = np.zeros((b, *shape), np.float32)
        np.asarray(exe(x))
        if trace:
            from work import lowered_kernels
            kernels_by_sig.update(lowered_kernels(
                exe.executable_for(x).lower(x).as_text(), KERNELS))
    if trace:
        exe = Spanned(exe, span)
    if wrap is not None:
        exe = wrap(exe)
    srv.point_executables[point] = exe
    pool = traffic.image_pool(mix, shape, seed)
    sched = traffic.schedule(mix, seed, seconds)
    sample = _Sample(mix["check_requests"], seed, max(mix["sizes"]))
    part = log_dir = None
    if trace:
        log_dir = trace_dir or tempfile.mkdtemp(prefix="bench-trace-")
        part = TracedPart(max(0.0, (seconds - TRACE_SECONDS) / 2),
                          min(TRACE_SECONDS, seconds))
    srv.start()
    try:
        for m in sorted(set(mix["sizes"])):     # one request of each size
            srv.submit(pool[:m]).result(timeout=600)
        # set-up's objects (graph, passes, traces) leave the collector's
        # reach: a full collection over them stalls every thread ~0.1 s
        gc.collect()
        gc.freeze()
        if part is not None:
            start_profiler(log_dir)
        before, compiles0 = _counters(srv), compiles.count
        gc_pauses = GcPauses()
        setup_s = time.perf_counter() - t_start
        drive = drive_open if mix["loop"] == "open" else drive_closed
        if part is not None:
            part.start()
        try:
            rec = drive(srv, mix, sched, pool, seconds, sample, span)
        finally:
            if part is not None:
                part.join()
                jax.profiler.stop_trace()
        gc_pauses.close()
        after = _counters(srv)
        rec["compiles_in_window"] = (compiles.count - compiles0
                                     + after["misses"] - before["misses"])
    finally:
        srv.stop(timeout=600)
        gc.unfreeze()
    mem = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    rec.update(cell=cell, setup_s=setup_s, window_s=seconds, chips=spec["chips"],
               stats={k: after[k] - before[k] for k in after},
               gc_pauses_s=[d for _, d in gc_pauses.pauses])
    if part is not None:
        import trace as trace_mod
        from work import event_call, useful_ops_per_image
        events = trace_mod.read_events(trace_mod.find_xplane(log_dir))
        on_trace = events.since_origin
        red = trace_mod.reduce(
            events, lambda name: event_call(name, kernels_by_sig), peaks,
            (on_trace(part.w0), on_trace(part.w1)),
            [(n, on_trace(a), on_trace(b)) for n, a, b in span.spans])
        if trace_dir is None:
            shutil.rmtree(log_dir, ignore_errors=True)
        rec.update(_rates(rec, part, seconds), trace=red, peaks=peaks,
                   useful_ops_per_image=useful_ops_per_image(cfg))
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
    # the program's state goes before the reference runs on the chip
    samples = sample.items()
    del srv, result, exe, sample
    gc.collect()
    correct, numbers = check(cfg, params, calib, pool, samples)
    # a request refused, lost or failed in the window is an answer that
    # never came
    numbers["failed_requests"] = {"value": int(rec["failed"]), "limit": 0}
    correct = correct and rec["failed"] == 0
    if keep is not None:
        keep.update(rec)

    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in metrics_for(bench, cell, kind):
        value = reader(m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out = {"correct": bool(correct), "attempted": int(rec["attempted"]),
           "failed": int(rec["failed"]), "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = {"device_ops": rec["trace"]["device_ops"],
                            "idle_gaps": rec["trace"]["idle_gaps"]}
    gcs = rec["gc_pauses_s"]
    out["host"] = {"gc_n": len(gcs), "gc_max_ms": 1e3 * max(gcs, default=0.0),
                   "gen_lag_max_ms": 1e3 * float(np.max(rec["gen_lag_s"],
                                                        initial=0.0))}
    if trace:
        out["host"].update(window_images_per_s=rec["window_images_per_s"],
                           traced_images_per_s=rec["traced_images_per_s"])
    out["readings"] = {k: v["value"] for k, v in numbers.items()
                       if v["limit"] is None}
    out["check"] = {k: v for k, v in numbers.items() if v["limit"] is not None}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in out["check"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
