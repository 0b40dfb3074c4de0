"""Device planes (mat/device_plane.py): the host's time for one device
dispatch in the traced slice, from argument preparation
(``device_prepare``) through the enqueue (``device_dispatch``) to the
values on the host (``device_fetch``, which waits for the device), per
dispatch.  From ``obs.prof.last_capture()``.  Moves ``read_p95_ms``:
a read is one or two of these end to end."""

PARTS = ("device_prepare", "device_dispatch", "device_fetch")
DISPATCHES = ("device_dispatch",)


def read(w):
    if not w.trace:
        return None
    from antidote_tpu.obs import prof

    cap = getattr(prof, "last_capture", lambda: None)()
    spans = (cap or {}).get("spans", {})
    n = sum(spans[s]["count"] for s in DISPATCHES if s in spans)
    if not n:
        return 0.0
    return 1000.0 * sum(spans[s]["total_s"] for s in PARTS
                        if s in spans) / n
