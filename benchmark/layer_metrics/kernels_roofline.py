"""Kernels (mat/store.py, mat/pallas_kernels.py): the least time the
chip's HBM could take to move the bytes that the work answered in the
traced slice needs (trace.needed_bytes: keys read and operations
appended, times their plane's row bytes) over the time the device was
busy in that slice.  Bandwidth-bound by construction: the store
programs gather and scatter rows and do next to no arithmetic.  Moves
``txn_per_s``."""


def read(w):
    t = w.trace
    if not t or not t.get("needed_bytes") or not t["busy_s"]:
        return None
    return 100.0 * t["needed_bytes"] / t["hbm_bytes_per_s"] / t["busy_s"]
