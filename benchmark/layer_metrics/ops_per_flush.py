"""Ingest (mat/ingest.py): update operations acknowledged per device
flush of any kind over the window.  More per flush is fewer scatters
per commit; it moves ``update_p95_ms``."""


def read(w):
    flushes = w.counters["ingest_flushes"]
    return w.update_ops / flushes if flushes and w.update_ops else None
