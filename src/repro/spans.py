"""Span recorder for the program's own layer boundaries.

Off by default.  While off, :func:`active` returns ``None`` and a hot-path
site pays one ``None`` check and allocates nothing::

    rec = spans.active()
    if rec is not None:
        rec.add("serve.assemble", t0, time.time_ns(), batch=bid)

Cold paths (set-up, compiles) use the context-manager form,
``with spans.span("flow.transform"): ...``.

A span is ``(name, start_ns, end_ns, track, ids)``: wall-clock nanoseconds
(``time.time_ns``, the clock a profiler trace's ``profile_start_time``
counts from, so spans and device events meet by one subtraction), the
recording thread's name as ``track`` (or :data:`REQUESTS` for per-request
spans, which no thread owns), and a dict of ids (``batch``, ``rid``, ...).
Spans live in memory in a bounded buffer; once it is full, later spans are
counted in ``dropped`` and discarded.  A few named counters sit beside them.
:func:`snapshot` hands everything out.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List, NamedTuple, Optional

__all__ = ["REQUESTS", "Recorder", "Span", "active", "disable", "enable",
           "snapshot", "span"]

# the track of per-request spans (serve.queue): they belong to no thread
REQUESTS = "requests"
CAPACITY = 1 << 20          # spans kept before ``dropped`` counts the rest


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    track: str
    ids: Dict[str, object]


class Recorder:
    """The spans and counters recorded since :func:`enable`.

    ``add`` appends one flat tuple without a lock (one ``list.append`` is
    atomic): ``(name, start_ns, end_ns, track, *keys, *values)``.  It holds
    no container, so the garbage collector stops tracking it at its first
    pass: a span holding a dict would stay tracked, and each full collection
    would scan every span of a window while all threads wait.
    :meth:`snapshot` turns them into :class:`Span`."""

    def __init__(self, capacity: int = CAPACITY):
        self.capacity = capacity
        self.spans: List[tuple] = []
        self.counters: Dict[str, int] = {}
        self.dropped = 0
        self._lock = threading.Lock()

    def add(self, name: str, start_ns: int, end_ns: int,
            track: Optional[str] = None, **ids) -> None:
        if len(self.spans) < self.capacity:
            self.spans.append((name, start_ns, end_ns,
                               track or threading.current_thread().name,
                               *ids, *ids.values()))
        else:
            with self._lock:
                self.dropped += 1

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + n

    def snapshot(self) -> dict:
        """``{"spans": [...], "counters": {...}, "dropped": n}``, copied."""
        with self._lock:
            return {"spans": [_span(s) for s in list(self.spans)],
                    "counters": dict(self.counters), "dropped": self.dropped}


def _span(flat: tuple) -> Span:
    n = (len(flat) - 4) // 2
    return Span(*flat[:4], dict(zip(flat[4:4 + n], flat[4 + n:])))


_active: Optional[Recorder] = None


def active() -> Optional[Recorder]:
    """The recorder, or ``None`` while recording is off."""
    return _active


def enable() -> Recorder:
    """Start recording into a fresh recorder and return it."""
    global _active
    _active = Recorder()
    return _active


def disable() -> Optional[Recorder]:
    """Stop recording; returns the recorder that was active, if any."""
    global _active
    rec, _active = _active, None
    return rec


def snapshot() -> Optional[dict]:
    """The active recorder's :meth:`Recorder.snapshot`, or ``None``."""
    rec = _active
    return None if rec is None else rec.snapshot()


class span:
    """``with span(name, **ids):`` records the block as one span while
    recording is on (cold paths: it allocates even while off)."""

    __slots__ = ("name", "track", "ids", "rec", "t0")

    def __init__(self, name: str, track: Optional[str] = None, **ids):
        self.name, self.track, self.ids = name, track, ids

    def __enter__(self) -> "span":
        self.rec = rec = _active
        self.t0 = 0 if rec is None else time.time_ns()
        return self

    def __exit__(self, *exc) -> bool:
        if self.rec is not None:
            self.rec.add(self.name, self.t0, time.time_ns(), self.track,
                         **self.ids)
        return False
