"""The one general load generator: a traffic mix is a data file
(``bench/traffic/<mix>.json``) of parameters, and this module turns it and a
seed into the exact requests a run sends.

Keys of a mix file:

* ``loop`` — ``"open"`` (independent users: Poisson arrivals at
  ``share_of_knee`` x ``knee_per_s`` requests per second, sent on schedule
  whatever the server does; the knee is the highest rate the cell's server
  sustained without a growing backlog, found once by a sweep on the chip) or
  ``"closed"`` (``clients`` callers, each sending its next request when the
  previous one is answered);
* ``sizes`` — the request-size mix (images per request), drawn uniformly;
* ``point`` — the working point the server is pinned to (``w8``, ...);
* ``server`` — ``max_batch``, ``buckets``, ``max_wait_s``,
  ``pipeline_depth``, ``queue_depth`` of the ``AccelServer``;
* ``pool_images`` — how many distinct seeded images the requests slice from;
* ``check_requests`` — how many finished requests the correctness check
  compares.

Every seed gets the same work in another order: an open schedule's sizes
are the mix tiled to the request count and its gaps are the exponential
distribution's quantiles, both permuted by the seed; a closed loop cycles
through the tiled mix in a seeded order.  So seeds differ in arrival order
and in which images are sent, never in how much is asked.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
# stream ids separating the seed's uses (the weights use 0)
_CALIB, _SCHEDULE, _POOL, _SAMPLE = 1, 2, 3, 4
CALIB_ROWS = 16
# length of a closed loop's size cycle, in copies of the mix
_CLOSED_CYCLE = 512


def load(name: str) -> dict:
    with open(TRAFFIC_DIR / f"{name}.json") as f:
        return json.load(f)


def rng(seed: int, stream: int) -> np.random.Generator:
    """A generator for one use of the seed (any whole number)."""
    return np.random.default_rng([seed % 2**63, stream])


def rate_per_s(mix: dict) -> float:
    """An open mix's offered rate (requests per second)."""
    return mix["share_of_knee"] * mix["knee_per_s"]


@dataclass
class Schedule:
    """The requests of one run: the k-th is ``sizes[k]`` images starting at
    pool row ``offsets[k]``, due ``due_s[k]`` seconds after the window opens
    (open loop; ``None`` for a closed loop, which sends as answers come)."""
    sizes: np.ndarray
    offsets: np.ndarray
    due_s: np.ndarray | None


def schedule(mix: dict, seed: int, seconds: float) -> Schedule:
    r = rng(seed, _SCHEDULE)
    sizes_mix = np.asarray(mix["sizes"], np.int64)
    if mix["loop"] == "open":
        rate = rate_per_s(mix)
        n = max(1, int(round(rate * seconds)))
        u = (np.arange(n) + 0.5) / n
        gaps = r.permutation(-np.log1p(-u) / rate)
        due = np.cumsum(gaps) - gaps[0]
    else:
        n = _CLOSED_CYCLE * len(sizes_mix)
        due = None
    sizes = r.permutation(np.resize(sizes_mix, n))
    offsets = r.integers(0, mix["pool_images"] - sizes + 1)
    return Schedule(sizes, offsets, due)


def calibration_rows(shape, seed: int) -> np.ndarray:
    """The ``CALIB_ROWS`` seeded images the program and the reference both
    calibrate their activation ranges on: uniform [0, 1) float32."""
    return rng(seed, _CALIB).random((CALIB_ROWS, *shape), np.float32)


def image_pool(mix: dict, shape, seed: int) -> np.ndarray:
    """The seeded images requests are cut from: uniform [0, 1) float32."""
    return rng(seed, _POOL).random((mix["pool_images"], *shape), np.float32)


class Reservoir:
    """A uniform sample of ``k`` finished requests, drawn from the seed
    (Algorithm R over requests in the order they finish)."""

    def __init__(self, k: int, seed: int):
        self.k = k
        self.items: list = []
        self.seen = 0
        self._rng = rng(seed, _SAMPLE)

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(item)
            return
        j = int(self._rng.integers(0, self.seen))
        if j < self.k:
            self.items[j] = item
