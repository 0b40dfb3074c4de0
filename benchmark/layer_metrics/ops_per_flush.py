"""Ingest (mat/ingest.py): update operations per device flush of any
kind over the window — each acknowledged operation once in every DC's
planes (the origin appends it, each other DC applies it), over the
flushes of every DC.  More per flush is fewer scatters per commit; it
moves ``update_p95_ms``."""


def read(w):
    flushes = w.counters["ingest_flushes"]
    ops = w.update_ops * w.dcs
    return ops / flushes if flushes and ops else None
