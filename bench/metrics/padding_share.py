"""Share (%) of executed rows that were zero padding up to a bucket
(AccelServer scheduler counters over the window).  Read as ``padding_share.sat``."""


def read(rec):
    s = rec["stats"]
    rows = s["scheduled_rows"] + s["padded_rows"]
    return 100.0 * s["padded_rows"] / rows if rows else None
