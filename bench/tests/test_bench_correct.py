"""The comparison that decides ``correct``, shown to fail: a whole run of
``mnist-cnn.open`` (weights, DesignFlow, the server, the open-loop window,
the reference check) on the CPU with the chip check skipped, once sound,
once with the lower-precision control (the program's W4 working point) in
the W8 point's place, and once per fault planted in the served executable.

mnist-cnn runs here at its published widths: the CPU holds it.  Off the
chip the ``qjax`` writer takes its exact-integer reference path, which gives
the kernels' answers code for code (0 steps, PERF.md).
"""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import run  # noqa: E402

CELL = "mnist-cnn.open"
SECONDS = 1.0
SEED = 2**33 + 12345    # wider than 32 bits, as the driver's seeds are


def _run(**kw):
    return run.run_cell(CELL, SEED, SECONDS, False, require_tpu=False, **kw)


def test_sound_run_is_correct():
    out = _run()
    assert out["correct"], out["check"]
    assert out["failed"] == 0
    assert set(out["metrics"]) == {"p50_ms", "setup_s"}
    assert list(out)[-1] == "check"


def test_control_w4_is_not_correct():
    out = _run(point="w4")
    assert not out["correct"], out["check"]


class _Fault:
    """The served executable with its answers altered where they are
    produced."""

    def __init__(self, exe, alter):
        self._exe, self._alter = exe, alter

    def __call__(self, *cols):
        return self._alter(np.asarray(self._exe(*cols)))

    def __getattr__(self, name):
        return getattr(self._exe, name)


FAULTS = {
    # rows handed to the wrong request (a demux or batch-assembly slip)
    "rows_rolled": lambda y: np.roll(y, 1, axis=0),
    # one answer per batch altered: its first row's logits reversed
    "answer_altered": lambda y: np.concatenate([y[:1, ::-1], y[1:]]),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(fault):
    out = _run(wrap=lambda exe: _Fault(exe, FAULTS[fault]))
    assert not out["correct"], (fault, out["check"])


class _Refusing:
    """The served executable failing every third batch once set-up's
    requests are through: the requests in such a batch are answered with
    an error, so they never get their logits."""

    def __init__(self, exe):
        self._exe, self._calls = exe, 0

    def __call__(self, *cols):
        self._calls += 1
        if self._calls > 16 and self._calls % 3 == 0:
            raise RuntimeError("planted batch failure")
        return self._exe(*cols)

    def __getattr__(self, name):
        return getattr(self._exe, name)


def test_failed_requests_are_not_correct():
    """Requests the window never answered make a run not correct, however
    well the answered ones compare: shedding load is not a faster server."""
    out = _run(wrap=_Refusing)
    assert out["failed"] > 0
    assert not out["correct"], out["check"]
    assert out["check"]["failed_requests"] == {"value": out["failed"],
                                               "limit": 0}
    assert out["check"]["qref_err"]["value"] <= out["check"]["qref_err"]["limit"]
