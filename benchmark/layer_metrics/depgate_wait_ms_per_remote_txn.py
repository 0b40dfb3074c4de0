"""Inter-DC dependency gate (interdc/dep.py): the mean time a remote
transaction waited in a DC's dependency gate over the window, from its
arrival (enqueue) to its apply — the histogram ``depgate_wait``'s sum
over its count, both as window deltas.  Only DCs past the origin apply
remote transactions, so it is theirs.  Moves ``vis_lag_p95_ms``: a
write is readable at the other DC only once applied there."""


def read(w):
    n = w.counters.get("depgate_wait_count", 0)
    return w.counters["depgate_wait_us"] / 1000.0 / n if n else None
