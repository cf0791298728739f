"""Reduction of a profiler trace (``.xplane.pb``, read with
``jax.profiler.ProfileData``) to the numbers the benchmark reports:

* the device's busy union and idle share over the traced window;
* device time per Pallas kernel, by the kernel's name, beside the least
  time the chip could have taken for the same calls (:mod:`work`);
* the device operations that took most time;
* the longest idle gaps, each labelled by the benchmark's host span that was
  open at the time (``bench.submit``, ``bench.execute``, ...).

The profile holds the device alone: :mod:`run` traces with the host tracer
off, because at host level 1 PJRT's own events (a transpose event per
chunk of every input copied to the chip) slow a run with large inputs
several times over.  The traced window and the host spans are kept by
:mod:`run` on the host's wall clock (``time.time_ns``) and handed in; the
trace's times count from its ``profile_start_time`` (the ``Task
Environment`` plane), a wall-clock time, so the two meet by one
subtraction.  Device planes are ``/device:TPU:<n>``; their operations are
the events of the ``XLA Ops`` line.
"""
from __future__ import annotations

import glob
import os
from collections import Counter
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from work import KernelCall, least_seconds

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
START_STAT = "profile_start_time"
TOP = 10

Interval = Tuple[int, int]          # (start_ns, end_ns)


@dataclass
class TraceEvents:
    """What the reduction needs from a trace: per device, its operations as
    (name, start_ns, end_ns), counted from ``origin_ns``, the wall-clock
    time (ns since the epoch) at which the profile started."""
    device_ops: Dict[str, List[Tuple[str, int, int]]] = field(
        default_factory=dict)
    origin_ns: Optional[int] = None

    def since_origin(self, t_ns: int) -> int:
        """A wall-clock time (``time.time_ns``) on the trace's clock."""
        return t_ns - self.origin_ns


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {files}")
    return files[0]


def read_events(path: str) -> TraceEvents:
    """Device operations and the profile's start time from an
    ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = TraceEvents()
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = out.device_ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.name, int(e.start_ns),
                                int(e.start_ns + e.duration_ns))
                               for e in line.events)
        stats = dict(plane.stats)
        if START_STAT in stats:
            out.origin_ns = int(stats[START_STAT])
    if out.origin_ns is None:
        raise ValueError(f"no {START_STAT} in {path}: host spans cannot be "
                         "placed on the trace's clock")
    return out


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of ``intervals``."""
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(busy: Sequence[Interval], window: Interval) -> List[Interval]:
    """The idle intervals of ``window`` left by the disjoint sorted
    ``busy``."""
    out, t = [], window[0]
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < window[1]:
        out.append((t, window[1]))
    return out


def label(t_ns: int, spans: Sequence[Tuple[str, int, int]]) -> str:
    """The innermost (shortest) benchmark span that covers ``t_ns``, else
    ``"idle host"``."""
    best = None
    for name, s, e in spans:
        if s <= t_ns < e:
            if best is None or e - s < best[1]:
                best = (name, e - s)
    return best[0] if best else "idle host"


def op_name(event_name: str) -> str:
    """A device operation's HLO instruction name (``%fusion.3``), without
    its shapes and operands."""
    return event_name.partition(" = ")[0]


def reduce(events: TraceEvents,
           call_of: Callable[[str], Optional[KernelCall]],
           peaks: dict, window: Interval,
           spans: Sequence[Tuple[str, int, int]] = ()) -> dict:
    """The trace's numbers over ``window``, averaged over the devices that
    ran anything; ``window`` and the host ``spans`` (name, start_ns, end_ns)
    are on the trace's clock (:meth:`TraceEvents.since_origin`):

    ``window_s``; ``busy_s``, the union of operation intervals;
    ``kernel_s`` and ``kernel_least_s``, per kernel name, the summed device
    time of the operations ``call_of`` names a call of that kernel and the
    summed least time of those calls (operations wholly inside the window);
    ``kernel_events``, their count; ``device_ops``, the ``TOP`` operations by
    summed time as ``[name, seconds]``; and ``idle_gaps``, the ``TOP``
    longest idle intervals as ``[label, seconds]``."""
    if window[1] <= window[0]:
        raise ValueError(f"empty traced window {window}")
    devices = {d: ops for d, ops in events.device_ops.items() if ops}
    if not devices:
        raise ValueError("no device operation in the trace")
    calls: Dict[str, Optional[KernelCall]] = {}
    busy_ns = 0
    per_op: Counter = Counter()
    spent: Counter = Counter()
    least: Counter = Counter()
    count: Counter = Counter()
    idle: List[Tuple[int, int]] = []
    for ops in devices.values():
        inside = [(n, max(s, window[0]), min(e, window[1]), s, e)
                  for n, s, e in ops if e > window[0] and s < window[1]]
        cover = union([(s, e) for _, s, e, _, _ in inside])
        busy_ns += sum(e - s for s, e in cover)
        idle.extend(gaps(cover, window))
        for name, s, e, s0, e0 in inside:
            per_op[op_name(name)] += e - s
            if s0 < window[0] or e0 > window[1]:
                continue
            if name not in calls:
                calls[name] = call_of(name)
            c = calls[name]
            if c is not None:
                spent[c.kernel] += e - s
                least[c.kernel] += least_seconds(c, peaks)
                count[c.kernel] += 1
    n = len(devices)
    idle.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "devices": n,
        "kernel_s": {k: v / n / 1e9 for k, v in spent.items()},
        "kernel_least_s": {k: v / n for k, v in least.items()},
        "kernel_events": dict(count),
        "device_ops": [[name, ns / n / 1e9]
                       for name, ns in per_op.most_common(TOP)],
        "idle_gaps": [[label((s + e) // 2, spans), (e - s) / 1e9]
                      for s, e in idle[:TOP]],
    }

