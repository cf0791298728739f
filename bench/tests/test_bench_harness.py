"""The harness on the CPU: every cell resolves to its files, the traffic is
a function of the seed alone, every metric reader reads a recorded run, and
a run with no TPU exits non-zero with no result."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
import traffic  # noqa: E402

BENCHMARK = run.benchmark()
CELLS = [c["name"] for c in BENCHMARK["workloads"]]
METRICS = BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]
RECORDS = BENCH / "tests" / "data" / "records.jsonl"


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    spec = run.cell_spec(BENCHMARK, cell)
    cfg = run.config(spec["config"])
    assert cfg["name"] == spec["config"]
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == cfg["name"])
    assert entry["file"] == f"bench/configs/{cfg['name']}.json"
    mix = traffic.load(spec["traffic"])
    assert mix["loop"] in ("open", "closed")
    assert mix["point"] in ("w8", "w4", "w2")
    kinds = {"open": {"p50_ms"}, "closed": {"images_per_s"}}
    e2e = {m["name"] for m in run.metrics_for(BENCHMARK, cell, "end_to_end")}
    assert e2e == kinds[mix["loop"]] | {"setup_s"}
    assert run.metrics_for(BENCHMARK, cell, "per_layer")


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_metric_has_reader(metric):
    assert callable(run.reader(metric))


def test_peaks_by_device_kind():
    assert run.peaks_for("TPU v5 lite")["int8_ops_per_s"] == 393e12
    with pytest.raises(KeyError):
        run.peaks_for("cpu")


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_schedule_is_a_function_of_the_seed(loop):
    mix = {"loop": loop, "knee_per_s": 625.0, "share_of_knee": 0.8,
           "sizes": [1, 1, 1, 2, 2, 3, 4, 5, 8], "pool_images": 64}
    a = traffic.schedule(mix, 2**35 + 1, 4.0)
    b = traffic.schedule(mix, 2**35 + 1, 4.0)
    c = traffic.schedule(mix, 7, 4.0)
    for x, y in ((a.sizes, b.sizes), (a.offsets, b.offsets)):
        np.testing.assert_array_equal(x, y)
    # another seed: the same work in another order
    assert not np.array_equal(a.sizes, c.sizes)
    np.testing.assert_array_equal(np.sort(a.sizes), np.sort(c.sizes))
    assert (a.offsets + a.sizes <= mix["pool_images"]).all()
    if loop == "open":
        np.testing.assert_array_equal(a.due_s, b.due_s)
        assert len(a.sizes) == 2000
        # the same gaps in another order (each leaves its first gap out: it
        # is the origin), so the same quantiles of the gaps
        q = [5, 25, 50, 75, 95]
        np.testing.assert_allclose(np.percentile(np.diff(a.due_s), q),
                                   np.percentile(np.diff(c.due_s), q),
                                   rtol=0.01)
        assert a.due_s[-1] == pytest.approx(4.0, rel=0.05)
    else:
        assert a.due_s is None


def test_pool_is_a_function_of_the_seed():
    mix = {"pool_images": 4}
    a = traffic.image_pool(mix, (3, 3, 1), 2**40)
    np.testing.assert_array_equal(a, traffic.image_pool(mix, (3, 3, 1), 2**40))
    assert not np.array_equal(a, traffic.image_pool(mix, (3, 3, 1), 5))
    assert a.dtype == np.float32 and 0 <= a.min() and a.max() < 1


def test_reservoir_keeps_k_drawn_from_the_seed():
    def sample(seed):
        r = traffic.Reservoir(5, seed)
        for i in range(100):
            r.offer(i)
        return r.items
    assert sample(3) == sample(3) and len(sample(3)) == 5
    assert sample(3) != sample(4)


def _records():
    with open(RECORDS) as f:
        return [json.loads(line) for line in f]


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_reader_reads_recorded_runs(metric):
    """Each reader returns a number on every recorded chip run of a cell it
    is reported in (``bench/tests/data/records.jsonl``: runs of each cell,
    traced ones among them; a per-layer metric reads the traced ones)."""
    m = next(x for x in METRICS if x["name"] == metric)
    kind = "per_layer" if m in BENCHMARK["per_layer"] else "end_to_end"
    read = run.reader(metric)
    seen = 0
    for rec in _records():
        rec["latency_s"] = np.asarray(rec["latency_s"])
        rec["gen_lag_s"] = np.asarray(rec["gen_lag_s"])
        if m.get("workloads") and rec["cell"] not in m["workloads"]:
            continue
        if kind == "per_layer" and "trace" not in rec:
            continue
        value = read(rec)
        assert isinstance(value, (int, float)) and np.isfinite(value), value
        seen += 1
    assert seen, f"no recorded run reports {metric}"


class _QueueFull(Exception):
    pass


class _RefusingServer:
    """A server that refuses every other request at ``submit`` and answers
    the rest at once."""

    def __init__(self):
        self.n = 0

    def submit(self, x):
        self.n += 1
        if self.n % 2 == 0:
            raise _QueueFull()
        return _Ticket(x)

    def result(self, tk):
        return tk.x.sum(axis=(1, 2, 3))[:, None]

    def drop(self, tk):
        pass


class _Ticket:
    def __init__(self, x):
        self.x = x

    def wait(self, timeout):
        return True


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_refused_requests_count_as_failed(loop):
    mix = {"loop": loop, "knee_per_s": 200.0, "share_of_knee": 1.0,
           "clients": 4, "sizes": [1, 2], "pool_images": 8}
    sched = traffic.schedule(mix, 3, 0.2)
    pool = traffic.image_pool(mix, (2, 2, 1), 3)
    sample = run._Sample(4, 3, 2)
    drive = run.drive_open if loop == "open" else run.drive_closed
    rec = drive(_RefusingServer(), mix, sched, pool, 0.2, sample, run._NoSpan)
    assert rec["attempted"] >= 2
    assert rec["failed"] == rec["attempted"] // 2
    assert len(rec["done_t"]) == rec["attempted"] - rec["failed"]


def test_run_without_tpu_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert "metrics" not in p.stdout
    assert "TPU" in p.stderr
