"""Inter-DC replication, from the sender to the stable time at the
other DC: the median of the probers' lags over the window, each from a
commit's acknowledgement at the origin DC to the answer of the read of
the same keys at the other DC at its commit clock, on the prober's
clock; a failed read counts with the time it waited.  The body beside
the tail that ``vis_lag_p95_ms`` holds to a bound: a stop of the whole
process moves the tail and leaves the median (PERF.md, section 2)."""

import numpy as np


def read(w):
    lags = w.vis_lag_s
    return float(np.median(lags)) * 1000.0 if lags else None
