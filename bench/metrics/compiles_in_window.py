"""Programs lowered or compiled by JAX, plus executable-cache misses, inside
the whole window.  Every bucket is compiled in set-up, so this should read
0.  Read as ``compiles_in_window.open`` and ``compiles_in_window.sat``."""


def read(rec):
    return rec["compiles_in_window"]
