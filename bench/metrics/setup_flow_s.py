"""Seconds of set-up spent in the graph compiler: the sum of the program's
``flow.*`` spans (``DesignFlow``'s transform, calibration and writers),
recorded in set-up.  Nothing when the record holds no such span."""


def read(rec):
    spans = rec.get("program", {}).get("spans_s", {})
    flow = [s for name, s in spans.items() if name.startswith("flow.")]
    return sum(flow) if flow else None
