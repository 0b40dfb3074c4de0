"""Remote apply (txn/manager.py ``apply_remote``, called by
interdc/dep.py): the time one remote transaction's apply into a DC's
partition manager and planes takes in the traced slice — the work span
``depgate_admit``'s total over its count, from
``obs.prof.last_capture()``.  Moves ``vis_lag_p95_ms``: the apply
stands between a transaction's arrival and its visibility."""


def read(w):
    if not w.trace:
        return None
    from antidote_tpu.obs import prof

    cap = getattr(prof, "last_capture", lambda: None)()
    row = (cap or {}).get("spans", {}).get("depgate_admit")
    if not row or not row["count"]:
        return None
    return 1000.0 * row["total_s"] / row["count"]
