"""Host process: the share of the traced slice in which at least one
of the server's threads was inside a work span and outside every wait
span (``host_busy_s`` of ``obs.prof.last_capture()``).  Read beside
``device_idle_pct``: near 100 with the chip idle says the one Python
process is the limit, well under 100 says the clients or the wire are.
Moves ``txn_per_s``."""


def read(w):
    if not w.trace:
        return None
    from antidote_tpu.obs import prof

    cap = getattr(prof, "last_capture", lambda: None)()
    if not cap or not cap["length_s"]:
        return 0.0
    return 100.0 * cap["host_busy_s"] / cap["length_s"]
