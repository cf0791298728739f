"""Readings behind the benchmark's fixed numbers, several runs in one
process (one chip, one JAX start-up):

    # the knee of an open cell: one run per offered rate
    python3 bench/calibrate.py --workload mnist-cnn.open --rates 1000,2000 --seconds 10
    # the compared numbers of sound runs, and of the lower-precision control
    python3 bench/calibrate.py --workload mnist-cnn.open --seeds 11,12,13 --seconds 10
    python3 bench/calibrate.py --workload mnist-cnn.open --seeds 11,12,13 --seconds 10 --point w4
    # how late a bare sleeping thread wakes: beside each run, or alone in a
    # process that never imports JAX
    python3 bench/calibrate.py --workload mnist-cnn.open --seeds 11 --lag-probe
    python3 bench/calibrate.py --lag-probe-only 20

Each run prints one JSON line: its seed, point, rate, ``correct``, the
compared numbers and the end-to-end (``--trace 0``) or per-layer
(``--trace 1``) metrics.  The benchmark's own runs never come here.
"""
from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import numpy as np  # noqa: E402

import run  # noqa: E402


def jsonable(rec: dict, max_items: int = 2000) -> dict:
    """A run record as JSON: arrays as lists of at most ``max_items``."""
    def conv(v):
        if isinstance(v, dict):
            return {str(k): conv(x) for k, x in v.items()}
        if isinstance(v, (list, tuple, np.ndarray)):
            return [conv(x) for x in list(v)[:max_items]]
        if isinstance(v, np.generic):
            return v.item()
        return v
    return conv(rec)


class LagProbe(threading.Thread):
    """A thread that sleeps 1 ms at a time and records how late it wakes.
    A stall of the whole process, or of the machine under it, shows as one
    late wake-up, whatever else the process is doing."""

    SLEEP_S = 0.001

    def __init__(self):
        super().__init__(name="bench-lag-probe")
        self.wakes = []
        self._halt = threading.Event()

    def run(self):
        while not self._halt.is_set():
            t = time.perf_counter()
            time.sleep(self.SLEEP_S)
            self.wakes.append((t, time.perf_counter() - t - self.SLEEP_S))

    def halt(self):
        self._halt.set()
        self.join()

    def summary(self, lo: float = float("-inf"),
                hi: float = float("inf")) -> dict:
        """Late wake-ups (ms) of the sleeps begun in ``[lo, hi)``."""
        late = np.asarray([d for t, d in self.wakes if lo <= t < hi]) * 1e3
        if not len(late):
            return {"n": 0}
        return {"n": len(late), "p50_ms": float(np.percentile(late, 50)),
                "p99_ms": float(np.percentile(late, 99)),
                "max_ms": float(late.max()),
                "over_20ms": int((late > 20).sum()),
                "over_50ms": int((late > 50).sum()),
                "over_100ms": int((late > 100).sum())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload")
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--point", default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep each traced run's profile under this directory")
    ap.add_argument("--record", default=None,
                    help="write each run's record here as JSON lines")
    ap.add_argument("--lag-probe", action="store_true",
                    help="run LagProbe beside each run; report its window")
    ap.add_argument("--lag-probe-only", type=float, default=None,
                    metavar="SECONDS",
                    help="run LagProbe alone, with no JAX, and exit")
    args = ap.parse_args(argv)
    if args.lag_probe_only is not None:
        probe = LagProbe()
        probe.start()
        time.sleep(args.lag_probe_only)
        probe.halt()
        print(json.dumps({"lag_probe_alone": probe.summary()}), flush=True)
        return 0
    if args.workload is None:
        ap.error("--workload is needed unless --lag-probe-only is given")
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = [float(r) for r in args.rates.split(",")] if args.rates else [None]
    for rate in rates:
        for seed in seeds:
            rec: dict = {}
            probe = LagProbe() if args.lag_probe else None
            if probe is not None:
                probe.start()
            try:
                out = run.run_cell(args.workload, seed, args.seconds,
                                   bool(args.trace), point=args.point,
                                   rate=rate, keep=rec,
                                   trace_dir=args.trace_dir and
                                   f"{args.trace_dir}/{seed}",
                                   t_start=time.perf_counter())
            except run.NoChip as e:
                print(f"calibrate: {e}", file=sys.stderr)
                return 2
            finally:
                if probe is not None:
                    probe.halt()
            if probe is not None:
                out["lag_probe_window"] = probe.summary(
                    rec["t_open"], rec["t_open"] + args.seconds)
            print(json.dumps({"seed": seed, "point": args.point,
                              "rate": rate, **out}), flush=True)
            if args.record:
                with open(args.record, "a") as f:
                    f.write(json.dumps(jsonable(rec)) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
