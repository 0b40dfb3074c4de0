"""Durable log (oplog/log.py): log records made durable per fsync over
the window, counters ``log_group_records`` / ``log_fsyncs``.  More per
fsync is more commits sharing one wait for the disk; it moves
``txn_per_s``.  A checkpoint's ``sync()`` counts in both: its fsync,
and the records it found written and not yet synced.  Records, not
commits: under ``update90-uniform`` one commit alone leaves about 4.5
records in a partition's log, so 4.5 here is no sharing at all and
PR 33's 5.2 is 1.15 commits an fsync; the value follows the mix's
transaction size until commits share drains."""


def read(w):
    fsyncs = w.counters["log_fsyncs"]
    return w.counters["log_group_records"] / fsyncs if fsyncs else None
