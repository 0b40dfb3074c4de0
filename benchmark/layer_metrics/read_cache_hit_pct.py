"""Partition manager (txn/manager.py): the share of snapshot reads the
frontier-keyed value cache answered, over the window.  Moves
``read_p95_ms``: a hit never asks the device."""


def read(w):
    hits = w.counters["read_cache_hits"]
    looked = hits + w.counters["read_cache_misses"]
    return 100.0 * hits / looked if looked else None
