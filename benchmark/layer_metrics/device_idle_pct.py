"""Device: the share of the traced slice in which no operation ran on
the chip.  Moves ``txn_per_s``."""


def read(w):
    t = w.trace
    if not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
