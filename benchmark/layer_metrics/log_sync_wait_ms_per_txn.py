"""Durable log (oplog/partition.py): the time committers slept on their
durability tickets in the traced slice — the wait span
``log_sync_wait``, one for every partition a commit wrote under
``sync_log`` true — per request answered in it.  From
``obs.prof.last_capture()``; 0.0 where the capture holds no such span
(``sync_log`` false) or holds it as an instant, as programs before
PR 33 record it.  Moves ``update_p95_ms``: the acknowledgement waits
for the fsync."""


def read(w):
    if not w.trace:
        return None
    from antidote_tpu.obs import prof

    cap = getattr(prof, "last_capture", lambda: None)()
    if not cap or not cap["requests_answered"]:
        return 0.0
    waited = cap["spans"].get("log_sync_wait", {}).get("total_s", 0.0)
    return 1000.0 * waited / cap["requests_answered"]
