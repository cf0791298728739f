"""Useful rows per scheduled batch (AccelServer scheduler counters) over the
window: how well the front end coalesced requests.  Read as ``batch_rows.open`` and ``batch_rows.sat``."""


def read(rec):
    s = rec["stats"]
    return s["scheduled_rows"] / s["scheduled_batches"] \
        if s["scheduled_batches"] else None
