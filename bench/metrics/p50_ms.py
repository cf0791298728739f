"""Median client-side latency (ms) of the requests due in the window, each
timed from when it was due until its answer was taken."""
import numpy as np


def read(rec):
    lat = rec["latency_s"]
    return float(np.percentile(lat, 50)) * 1e3 if len(lat) else None
