"""A configuration brings its own model, kernel count and program counter
as new files only: under a directory of the test's own, a model module (a
copy of bench/models/cnn.py whose ``to_ir`` also counts a program counter
on the span recorder), its configuration, a kernel's operation count and a
reader of that counter.  The harness, pointed at that directory before its
own, runs a cell of the new configuration through ``run.run_cell`` on the
CPU: it builds, serves, passes ``check`` and reports the reader's value.
The span recorder is off all through the window."""
import json
import sys
from pathlib import Path


BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import hooks  # noqa: E402
import run  # noqa: E402
import work  # noqa: E402
from repro import spans  # noqa: E402

SEED = 2**33 + 777      # wider than 32 bits, as a run's --seed may be
COUNTER = "ext.graphs_read"

TO_IR_COUNTING = f'''

_to_ir = to_ir


def to_ir(cfg, params):
    from repro import spans
    spans.active().count({COUNTER!r})
    return _to_ir(cfg, params)
'''

READER = f'''
def read(rec):
    return rec["program"]["counters"].get({COUNTER!r})
'''

KERNEL = '''
def count(operands, result):
    return 7, 11
'''


def _new_files(root: Path) -> dict:
    for kind in ("configs", "models", "kernels", "metrics"):
        (root / kind).mkdir()
    (root / "models" / "cnn_copy.py").write_text(
        (BENCH / "models" / "cnn.py").read_text() + TO_IR_COUNTING)
    cfg = json.loads((BENCH / "configs" / "mnist-cnn.json").read_text())
    cfg.update(name="ext-cnn", family="cnn_copy")
    (root / "configs" / "ext-cnn.json").write_text(json.dumps(cfg))
    (root / "kernels" / "ext_kernel.py").write_text(KERNEL)
    (root / "metrics" / "ext_graphs.py").write_text(READER)
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell = "ext-cnn.open"
    bench["configs"].append({"name": "ext-cnn", "source": cfg["source"],
                             "file": "ext/configs/ext-cnn.json",
                             "reduced": [], "why": "a test's configuration"})
    bench["workloads"].append({"name": cell, "config": "ext-cnn",
                               "traffic": "open-mnist-cnn", "chips": 1,
                               "why": "a test's cell"})
    next(m for m in bench["end_to_end"]
         if m["name"] == "p50_ms")["workloads"].append(cell)
    bench["end_to_end"].append({"name": "ext_graphs", "unit": "count",
                                "better": "lower", "bound": 0.25,
                                "source": "host_clock", "workloads": [cell]})
    return bench


def test_new_files_make_a_cell(tmp_path, monkeypatch):
    bench = _new_files(tmp_path)
    for kind in hooks.DIRS:
        monkeypatch.setitem(hooks.DIRS, kind,
                            [tmp_path / kind, *hooks.DIRS[kind]])
    monkeypatch.setattr(run, "benchmark", lambda: bench)

    # the recorder, seen where the window opens and by every served batch
    seen = []
    drive_open = run.drive_open

    def drive(*args, **kw):
        seen.append(("window", spans.active()))
        return drive_open(*args, **kw)

    class Watched:
        def __init__(self, exe):
            self._exe = exe

        def __call__(self, *cols):
            seen.append(("batch", spans.active()))
            return self._exe(*cols)

        def __getattr__(self, name):
            return getattr(self._exe, name)

    monkeypatch.setattr(run, "drive_open", drive)
    rec = {}
    out = run.run_cell("ext-cnn.open", SEED, 1.0, False, require_tpu=False,
                       wrap=Watched, keep=rec)

    assert out["correct"], out["check"]
    assert out["failed"] == 0
    assert out["metrics"]["ext_graphs"] == {"value": 1, "unit": "count"}
    assert set(out["metrics"]) == {"p50_ms", "setup_s", "ext_graphs"}
    assert [w for w, _ in seen].count("window") == 1
    assert len(seen) > 10 and all(r is None for _, r in seen)
    assert spans.active() is None
    program = rec["program"]
    assert program["counters"] == {COUNTER: 1}
    assert not [n for n in program["spans_s"] if n.startswith("serve.")]
    assert {"flow.transform", "flow.calibrate", "flow.write",
            "exe.compile"} <= set(program["spans_s"])
    assert all(s > 0 for s in program["spans_s"].values())
    assert program["dropped"] == 0
    # the kernel's count file is found by the kernel's name; a kernel with
    # none is not counted
    shapes = ([((8, 128), 1)], [((8, 128), 1)])
    assert work.count("ext_kernel", *shapes) == work.KernelCall(
        "ext_kernel", 7, 11)
    assert work.count("no_such_kernel", *shapes) is None
