"""The host process's account: where its CPU goes and what stops it.

A server bound by one interpreter is measured by the CPU a transaction
costs and by what holds the other threads still; three accounts say
so, each kept where it is written and read only when asked for:

- **The collector's stops.** A ``gc.callbacks`` entry, installed once
  per process by :func:`install` (``obs.configure`` calls it), times
  every collection by generation.  While the tracer records on the
  collecting thread it also records a ``gc_collect`` work span
  (``generation``, ``collected``) under that thread's innermost span,
  and inside a profiler capture holds a ``TraceAnnotation`` of that
  name (``Tracer.open_timed``).
- **The partition locks' holds by acquiring site.** Each partition
  manager's condition (``txn/manager.py`` ``_SiteCondition``) keeps its
  own table of :class:`LockSite`, written by the holder; the table is
  listed here (:func:`track_lock_table`) and summed by
  :func:`lock_sites`.
- **CPU.** The whole process's (``time.process_time``: every thread,
  the interpreter's and the native runtime's) and each Python thread's
  own clock, grouped by :func:`thread_kind`.

``stats.registry`` reads them when scraped (``antidote_pm_lock_*``,
``antidote_gc_*``, ``antidote_thread_cpu_seconds_total``,
``process_cpu_seconds_total``); ``obs.prof`` takes :func:`account` as a
capture opens and closes, and ``last_capture()["host"]`` is the
:func:`difference`; ``tools/host_cpu_probe.py`` prints the same for a
window of its own.
"""

from __future__ import annotations

import gc
import re
import threading
import time

from antidote_tpu.obs.spans import tracer

# ------------------------------------------------------- the collector


#: pause (ns) and passes by generation, since install(); written by the
#: collecting thread, and collections never overlap
_gc_ns = [0, 0, 0]
_gc_n = [0, 0, 0]
_gc_t0 = 0
_gc_open = None
_install_lock = threading.Lock()


def _on_gc(phase: str, info: dict) -> None:
    global _gc_t0, _gc_open
    if phase == "start":
        _gc_t0 = time.perf_counter_ns()
        _gc_open = tracer.open_timed("gc_collect")
        return
    if not _gc_t0:      # installed while a collection ran
        return
    gen = info["generation"]
    _gc_ns[gen] += time.perf_counter_ns() - _gc_t0
    _gc_n[gen] += 1
    if _gc_open is not None:
        opened, _gc_open = _gc_open, None
        tracer.close_timed(opened, "gc_collect", "host", generation=gen,
                           collected=info["collected"])


def install() -> None:
    """Time the collector's passes from now on (once per process)."""
    with _install_lock:
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)


def gc_pauses() -> dict:
    """``{generation: (pause seconds, passes)}`` since :func:`install`."""
    return {g: (_gc_ns[g] / 1e9, _gc_n[g]) for g in range(3)}


# --------------------------------------------------- the partition locks


class LockSite:
    """One acquiring site's account of one lock (nanoseconds)."""

    __slots__ = ("holds", "held_ns", "waits", "waited_ns", "sleeps",
                 "slept_ns")

    def __init__(self):
        self.holds = self.held_ns = self.waits = self.waited_ns = 0
        self.sleeps = self.slept_ns = 0


#: every partition lock's table of sites.  A table outlives its
#: partition (a few hundred bytes), so the sums never fall back when a
#: partition is closed or handed off
_LOCK_TABLES: list = []


def track_lock_table(table: dict) -> None:
    _LOCK_TABLES.append(table)


def lock_sites() -> dict:
    """The locks' tables summed by site: ``{site: {field: int}}``."""
    out: dict = {}
    for table in list(_LOCK_TABLES):
        # dict(...) first: a holder may add a site while this copies
        for site, st in dict(table).items():
            d = out.get(site)
            if d is None:
                d = out[site] = dict.fromkeys(LockSite.__slots__, 0)
            for k in LockSite.__slots__:
                d[k] += getattr(st, k)
    return out


# --------------------------------------------------------------- the CPU


def thread_kind(name: str) -> str:
    """Threads of one pool under one name: an unnamed thread by its
    target (``Thread-7 (serve_forever)``), a named one with its number
    off."""
    m = re.match(r"Thread-\d+ \((.*)\)$", name)
    if m:
        # socketserver.ThreadingMixIn starts one of these a connection
        return ("handlers" if m.group(1) == "process_request_thread"
                else m.group(1))
    return re.sub(r"[-_ ]?\d+$", "", name.strip()) or "?"


_cpu_lock = threading.Lock()
#: thread -> (kind, CPU seconds at the last reading)
_cpu_seen: dict = {}
#: kind -> CPU seconds of its threads that have ended since
_cpu_ended: dict = {}


def thread_cpu() -> dict:
    """``{kind: CPU seconds}`` of the Python threads, each on its own
    clock.  A counter: a thread that ends keeps what it was last read
    at in its kind."""
    alive = {}
    for t in threading.enumerate():
        if t.ident is None:
            continue
        try:
            s = time.clock_gettime(time.pthread_getcpuclockid(t.ident))
        except OSError:     # ended since enumerate()
            continue
        alive[t] = (thread_kind(t.name), s)
    with _cpu_lock:
        for t, (kind, s) in _cpu_seen.items():
            if t not in alive:
                _cpu_ended[kind] = _cpu_ended.get(kind, 0.0) + s
        _cpu_seen.clear()
        _cpu_seen.update(alive)
        out = dict(_cpu_ended)
    for kind, s in alive.values():
        out[kind] = out.get(kind, 0.0) + s
    return out


# -------------------------------------------------------------- together


def account() -> dict:
    """Every account now (what :func:`difference` takes)."""
    return {"t": time.monotonic(), "process_cpu_s": time.process_time(),
            "thread_cpu_s": thread_cpu(), "lock_sites": lock_sites(),
            "gc": gc_pauses()}


def difference(a: dict, b: dict) -> dict:
    """What the process did between two accounts: seconds of CPU, the
    locks' holds, waits and sleeps (over every partition, and by site)
    and the collector's pauses by generation."""
    threads = {k: s - a["thread_cpu_s"].get(k, 0.0)
               for k, s in b["thread_cpu_s"].items()}
    sites = {}
    for site, d in b["lock_sites"].items():
        was = a["lock_sites"].get(site)
        d = {k: v - (was[k] if was else 0) for k, v in d.items()}
        if d["holds"] or d["waits"] or d["sleeps"]:
            sites[site] = {
                "holds": d["holds"], "held_s": d["held_ns"] / 1e9,
                "waits": d["waits"], "waited_s": d["waited_ns"] / 1e9,
                "sleeps": d["sleeps"], "slept_s": d["slept_ns"] / 1e9}
    lock = {k: sum(s[k] for s in sites.values()) for k in (
        "holds", "held_s", "waits", "waited_s", "sleeps", "slept_s")}
    gc_by = {g: (b["gc"][g][0] - a["gc"][g][0],
                 b["gc"][g][1] - a["gc"][g][1]) for g in range(3)}
    python_s = sum(threads.values())
    process_s = b["process_cpu_s"] - a["process_cpu_s"]
    return {
        "length_s": b["t"] - a["t"],
        "process_cpu_s": process_s,
        "python_threads_cpu_s": python_s,
        "native_cpu_s": process_s - python_s,
        "thread_cpu_s": threads,
        "pm_lock": lock, "pm_lock_sites": sites,
        "gc_pause_s": {g: p for g, (p, _n) in gc_by.items()},
        "gc_collections": {g: n for g, (_p, n) in gc_by.items()},
    }
