"""Wire server, API, coordinator (pb/server.py, api.py,
txn/coordinator.py): the self time of their work spans in the traced
slice (a span's duration less the part its children cover, the request
roots' among them: the handlers' conversions between wire and program
types) per request answered in it.  From ``obs.prof.last_capture()``.
Moves ``txn_per_s``: it is the host's Python above the partition
manager, which every transaction passes."""

LAYER_CATS = ("wire", "api", "coordinator")


def read(w):
    if not w.trace:
        return None
    from antidote_tpu.obs import prof

    cap = getattr(prof, "last_capture", lambda: None)()
    if not cap or not cap["requests_answered"]:
        return 0.0
    self_s = sum(row["self_s"] for row in cap["spans"].values()
                 if row["cat"] in LAYER_CATS and row["kind"] != "wait")
    return 1000.0 * self_s / cap["requests_answered"]
