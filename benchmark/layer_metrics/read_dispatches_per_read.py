"""Read serve (mat/serve.py): device fold dispatches per answered
``read_only_txn`` over the window.  Coalescing concurrent readers
lowers it; it moves ``read_p95_ms``."""


def read(w):
    reads = w.answered.get("read_only_txn", 0)
    return w.counters["read_dispatches"] / reads if reads else None
