"""From a profiler trace to device numbers, and the table of peaks.

A traced run captures a slice of the window through ``obs.prof`` (so the
program's kernels annotate the timeline) and this file reduces the
``.xplane.pb`` with ``jax.profiler.ProfileData``.  On a v5e the device
plane is ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per
operation and ``XLA Modules`` one per program (``jit_<name>(<hash>)``),
both on the host planes' clock (looked at by hand, PR 24).

``busy_s`` is the union of the operations' intervals inside the slice,
averaged over the device planes; ``window_s`` is the slice, marked in
the trace by a ``TraceAnnotation`` of the benchmark's own.  No device
plane, or no operation in it, is an error: ``busy_s`` is never 0.
"""

from __future__ import annotations

import contextlib
import functools
import glob
import os
import re
import time

SLICE_MARK = "bench_slice"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINES = ("XLA Ops", "XLA Modules")

#: peaks of one chip, keyed by ``device_kind``.  Source: Google Cloud
#: documentation, "TPU v5e" (16 GB HBM2e at 819 GB/s, 197 TFLOP/s bf16).
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flop_per_s": 197e12},
}


class TraceError(Exception):
    """The trace cannot give a device number."""


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise TraceError(f"no peaks are known for device kind "
                         f"{device_kind!r}; add it to PEAKS with its "
                         f"source") from None


@contextlib.contextmanager
def capture(log_dir: str, slice_s: float):
    """Trace ``slice_s`` seconds into ``log_dir``.  The capture opens
    through ``obs.prof`` so wrapped kernels name themselves; JAX's
    Python tracer is switched off for its length (a served window makes
    hundreds of thousands of Python calls a second, and tracing them
    slows the host that is being measured)."""
    import jax

    from antidote_tpu.obs import prof

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    start_trace = jax.profiler.start_trace
    jax.profiler.start_trace = functools.partial(
        start_trace, profiler_options=opts)
    try:
        with prof.profile(log_dir):
            with jax.profiler.TraceAnnotation(SLICE_MARK):
                t0 = time.monotonic()
                yield
                left = slice_s - (time.monotonic() - t0)
                if left > 0:
                    time.sleep(left)
    finally:
        jax.profiler.start_trace = start_trace


def xplane_of(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise TraceError(f"the profiler wrote no .xplane.pb under "
                         f"{log_dir}")
    return found[-1]


def union_ns(intervals: list) -> int:
    """Total length covered by ``(start, end)`` intervals that may
    overlap or nest."""
    total, reach = 0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total


def _gaps(intervals: list, lo: int, hi: int) -> list:
    """The idle stretches of ``[lo, hi]`` between merged intervals."""
    out, reach = [], lo
    for start, end in sorted(intervals):
        if start > reach:
            out.append((reach, start))
        reach = max(reach, end)
    if hi > reach:
        out.append((reach, hi))
    return out


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def reduce_xplane(path: str, top: int = 10) -> dict:
    """``busy_s``, ``window_s`` and the breakdown of one trace."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    device, host, mark = [], [], None
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            device.append(lines)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == SLICE_MARK:
                        mark = (int(e.start_ns),
                                int(e.start_ns + e.duration_ns))
                    elif line.name.startswith("python"):
                        host.append((int(e.start_ns),
                                     int(e.start_ns + e.duration_ns),
                                     e.name))
    if not device:
        raise TraceError(f"{path}: no device plane (/device:TPU:<n>) in "
                         "the trace: the device metrics cannot be read")
    busy, by_name, gaps, lo_hi = [], {}, [], []
    for lines in device:
        ops = next((lines[n] for n in OPS_LINES if n in lines), None)
        spans = [(int(e.start_ns), int(e.start_ns + e.duration_ns))
                 for e in ops.events] if ops is not None else []
        if not spans:
            raise TraceError(f"{path}: a device plane holds no "
                             "operation: nothing ran on the device "
                             "inside the traced slice")
        lo, hi = mark if mark else (min(s for s, _ in spans),
                                    max(e for _, e in spans))
        spans = [(max(s, lo), min(e, hi)) for s, e in spans
                 if e > lo and s < hi]
        if not spans:
            raise TraceError(f"{path}: no device operation inside the "
                             "marked slice")
        busy.append(union_ns(spans))
        lo_hi.append((lo, hi))
        gaps += _gaps(spans, lo, hi)
        for e in (lines.get("XLA Modules") or ops).events:
            name = _module_name(e.name)
            by_name[name] = by_name.get(name, 0) + int(e.duration_ns)
    lo, hi = lo_hi[0]
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = []
    for start, end in gaps[:top]:
        doing = [(min(e, end) - max(s, start), name)
                 for s, e, name in host if e > start and s < end]
        idle.append([max(doing)[1] if doing else "no_host_event",
                     (end - start) / 1e9])
    ops_top = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_planes": len(device),
        "breakdown": {
            "device_ops": [[n, d / len(device) / 1e9] for n, d in ops_top],
            "idle_gaps": idle,
        },
    }


# ------------------------------------------------ bytes the work needs


def plane_row_bytes(db) -> dict:
    """Per plane type: the bytes of one key's row over all the plane's
    per-key tables, and of one operation's row in its op ring — from
    the planes' own shapes.  A map keeps no table of its own
    (``MapPlane`` has no ``st``): its work is counted in its fields'
    planes (``rows_of_work``)."""
    import jax

    lanes = db.node.config.device_lanes
    out: dict = {}
    for pm in db.node.partitions:
        for name, plane in pm.device.planes.items():
            st = getattr(plane, "st", None)
            ops = getattr(st, "ops", None)
            if ops is None or name in out:
                continue
            capacity = ops.shape[0] // lanes
            key_row = op_row = 0.0
            for leaf in jax.tree_util.tree_leaves(st):
                if not leaf.shape:
                    continue
                if leaf.shape[0] == capacity:
                    key_row += leaf.nbytes / capacity
                elif leaf.shape[0] == ops.shape[0]:
                    op_row += leaf.nbytes / ops.shape[0]
            out[name] = {"key_row": key_row + lanes * op_row,
                         "op_row": op_row}
    return out


#: a record type -> the plane that keeps which fields a record holds,
#: read with them (mat/device_plane.py ``MapPlane``: a ``map_go``'s
#: presence is a ``set_go`` plane; a ``map_rr`` field is visible by its
#: own state)
PRESENCE = {"map_go": "set_go"}


def rows_of_work(ks, records: list, dcs: int = 1) -> tuple:
    """(key rows read, operation rows appended) by plane type over the
    answered ``records``, whatever kernel does the work: a flat key is
    one row of its type's plane; a record is a row of each field's
    plane and one of its presence plane; an update is one operation row
    of its type's plane, of a record one of its field's plane for each
    field it assigns; each operation once in every DC's planes."""
    keys_read: dict = {}
    ops: dict = {}

    def add(into: dict, t: str, n: int) -> None:
        into[t] = into.get(t, 0) + n

    for r in records:
        for k in r["read_keys"]:
            t = ks.type_of(k)
            rec = ks.record(t)
            if rec is None:
                add(keys_read, t, 1)
                continue
            for _f, field_type in rec.fields:
                add(keys_read, field_type, 1)
            if t in PRESENCE:
                add(keys_read, PRESENCE[t], 1)
        for k, _op, arg in r["updates"]:
            t = ks.type_of(k)
            if ks.record(t) is None:
                add(ops, t, dcs)
                continue
            for (_f, field_type), _assign in arg:
                add(ops, field_type, dcs)
    return keys_read, ops


def needed_bytes(rows: dict, keys_read: dict, ops_appended: dict) -> float:
    """The bytes the answered work has to move whatever kernel does it:
    every key read once over its whole row (base tables and live
    lanes), every appended operation written once."""
    return (sum(n * rows[t]["key_row"] for t, n in keys_read.items())
            + sum(n * rows[t]["op_row"] for t, n in ops_appended.items()))
