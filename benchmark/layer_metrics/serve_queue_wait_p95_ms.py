"""Read serve (mat/serve.py): the 95th percentile, over the read calls
staged in the traced slice, of the time from staging until a drain took
the call (span ``read_serve_queue_wait``).  From
``obs.prof.last_capture()``.  Moves ``read_p95_ms``: a read that finds
a drain in flight waits out that drain's device folds before its own
begin."""


def read(w):
    if not w.trace:
        return None
    from antidote_tpu.obs import prof

    cap = getattr(prof, "last_capture", lambda: None)()
    row = (cap or {}).get("spans", {}).get("read_serve_queue_wait")
    return 1000.0 * row["p95_s"] if row else 0.0
