"""Partition manager (txn/manager.py): the time requests slept in its
three waits in the traced slice — the partition lock, a prepared
transaction below the read's snapshot, the device readers' quiesce —
per request answered in it.  From ``obs.prof.last_capture()``.  Moves
``update_p95_ms``: a commit publishes under the lock and waits for the
readers to drain first."""

WAITS = ("pm_lock_wait", "pm_prepared_wait", "device_quiesce_wait")


def read(w):
    if not w.trace:
        return None
    from antidote_tpu.obs import prof

    cap = getattr(prof, "last_capture", lambda: None)()
    if not cap or not cap["requests_answered"]:
        return 0.0
    waited = sum(cap["spans"][n]["total_s"] for n in WAITS
                 if n in cap["spans"])
    return 1000.0 * waited / cap["requests_answered"]
