"""How late the open-loop generator sent its requests: the 95th percentile
(ms) of send time minus due time, over the requests due in the window.  A starved generator shows here, not as a
fast server."""
import numpy as np


def read(rec):
    lag = rec["gen_lag_s"]
    return float(np.percentile(lag, 95)) * 1e3 if len(lag) else None
