"""Share (%) of the middle of the window (run.TRACE_SECONDS) in which no
operation ran on the device (1 - busy union / that time, from the
device-only profiler trace).  Read as ``device_idle_share.open`` and ``device_idle_share.sat``."""


def read(rec):
    t = rec.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
