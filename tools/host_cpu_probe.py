#!/usr/bin/env python3
"""Where one interpreter's CPU goes under a cell's traffic (PR 34):

    python3 tools/host_cpu_probe.py --workload bb1dc.update90-uniform \\
        --seed 7 --seconds 15 [--tree <checkout>] [--out probe.json]

brings the cell's deployment up through the benchmark's own harness
(``benchmark/harness.py``: the load, the pattern warm-up, the client
processes, one window, the comparison that decides ``correct``) on
whatever backend JAX finds, and prints for the window

- the CPU-seconds of the server's Python threads by kind — the wire
  handlers, ``device-flusher``, the rest by name — each thread's own
  clock (``time.pthread_getcpuclockid``), and the whole process's
  beside them (``time.process_time``: what is left over ran in native
  threads, outside the interpreter);
- CPU-ms a transaction answered;
- every partition's ``pm._lock`` by acquiring site: holds and waits
  (count, total, mean, max), and the time a site slept on the
  condition with the lock given back.

A server bound by one interpreter reads about one CPU-second a second
whatever is done to its waits; what moves its throughput is CPU taken
off a transaction's path, and where the threads park says only where
they stand while one of them runs.  No cell runs this; ``--tree`` names
the checkout whose ``antidote_tpu`` and ``benchmark`` are imported, so
one copy of the tool reads a parent and a change.  ``--partitions``,
``--keys-per-partition`` and ``--clients`` shrink the cell for a
rehearsal off the chip, where the output is counts and shares, never
rates.  It instruments the process it starts and nothing else: the
program has no switch for it.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
import threading
import time

T_PROCESS_START = time.monotonic()


# ------------------------------------------------------ the partition lock


class SiteStat:
    """One acquiring site's account."""

    __slots__ = ("holds", "hold_s", "hold_max", "waits", "wait_s",
                 "wait_max", "sleeps", "sleep_s")

    def __init__(self):
        self.holds = self.waits = self.sleeps = 0
        self.hold_s = self.hold_max = 0.0
        self.wait_s = self.wait_max = self.sleep_s = 0.0

    def as_dict(self) -> dict:
        return {k: getattr(self, k) for k in self.__slots__}


class ProbedCondition:
    """A ``threading.Condition`` that keeps, for every function that
    takes it, how long the function waited for it and how long it held
    it.  It delegates to the condition it wraps, so threads that took
    the bare one before the swap and threads that take this one
    exclude each other as before.  A site is the name of the function
    that acquires (``_TimedLock.__enter__``'s caller, for the sites
    that go through ``pm._locked``); a re-entrant acquire belongs to
    the hold it is inside; ``wait`` closes the hold, counts its sleep
    and opens a new hold when it returns."""

    #: frames that acquire on a caller's behalf
    _THROUGH = ("__enter__", "acquire")

    def __init__(self, inner, stats: dict):
        self._inner = inner
        self._stats = stats          # site -> SiteStat, this lock's own
        self._tls = threading.local()

    def _site(self) -> str:
        f = sys._getframe(2)
        while f is not None and f.f_code.co_name in self._THROUGH:
            f = f.f_back
        return f.f_code.co_name if f is not None else "?"

    def _stat(self, site: str) -> SiteStat:
        st = self._stats.get(site)
        if st is None:
            st = self._stats.setdefault(site, SiteStat())
        return st

    def acquire(self, blocking: bool = True, timeout: float = -1):
        tls = self._tls
        if getattr(tls, "depth", 0):
            got = self._inner.acquire(blocking, timeout)
            if got:
                tls.depth += 1
            return got
        site = self._site()         # before the lock, not under it
        t0 = time.perf_counter()
        got = self._inner.acquire(False)
        waited = False
        if not got and blocking:
            waited = True
            got = self._inner.acquire(True, timeout)
        if got:
            now = time.perf_counter()
            # this lock's table is written with the lock held, so it
            # needs no lock of its own
            st = self._stat(site)
            if waited:
                w = now - t0
                st.waits += 1
                st.wait_s += w
                if w > st.wait_max:
                    st.wait_max = w
            tls.depth, tls.stat, tls.t_held = 1, st, now
        return got

    def release(self) -> None:
        tls = self._tls
        tls.depth -= 1
        if tls.depth == 0:
            self._close_hold(tls)
        self._inner.release()

    @staticmethod
    def _close_hold(tls) -> None:
        h = time.perf_counter() - tls.t_held
        st = tls.stat
        st.holds += 1
        st.hold_s += h
        if h > st.hold_max:
            st.hold_max = h

    __enter__ = acquire

    def __exit__(self, *exc):
        self.release()
        return False

    def wait(self, timeout=None):
        tls = self._tls
        self._close_hold(tls)
        t0 = time.perf_counter()
        try:
            return self._inner.wait(timeout)
        finally:
            now = time.perf_counter()
            tls.stat.sleeps += 1
            tls.stat.sleep_s += now - t0
            tls.t_held = now

    def notify(self, n: int = 1) -> None:
        self._inner.notify(n)

    def notify_all(self) -> None:
        self._inner.notify_all()


def probe_locks(db) -> list:
    """Put a :class:`ProbedCondition` in every partition manager's
    ``_lock`` (and in the ``_locked`` that times its waits for the
    spans); returns their tables of sites, one a partition."""
    from antidote_tpu.txn import manager

    tables = []
    for pm in db.node.partitions:
        tables.append({})
        probed = ProbedCondition(pm._lock, tables[-1])
        pm._lock = probed
        pm._locked = manager._TimedLock(probed, pm.partition)
    return tables


# ------------------------------------------------------------- the threads


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


def cpu_by_thread() -> dict:
    """{(ident, name): CPU-seconds so far} for every Python thread
    alive now.  A thread that ends between two readings drops out of
    their difference; the handlers and the background threads of a
    deployment live through a window."""
    out = {}
    for t in threading.enumerate():
        if t.ident is None:
            continue
        try:
            clk = time.pthread_getcpuclockid(t.ident)
            out[(t.ident, t.name)] = time.clock_gettime(clk)
        except OSError:     # ended since enumerate()
            continue
    return out


def snapshot(tables: list) -> dict:
    """The clocks now, and the partitions' lock tables summed by site.
    The tables' maxima start over, so the next snapshot's are those of
    the time between."""
    locks: dict = {}
    for table in tables:
        # dict(...) first: a holder may add a site while this copies
        for site, st in dict(table).items():
            d = locks.setdefault(site, dict.fromkeys(st.__slots__, 0))
            for k, v in st.as_dict().items():
                d[k] = max(d[k], v) if k.endswith("_max") else d[k] + v
            st.hold_max = st.wait_max = 0.0
    return {"t": time.monotonic(), "process_s": time.process_time(),
            "threads": cpu_by_thread(), "locks": locks}


def window_account(s0: dict, s1: dict) -> dict:
    """The difference of two snapshots, grouped."""
    kinds: dict = {}
    for key, cpu1 in s1["threads"].items():
        d = cpu1 - s0["threads"].get(key, 0.0)
        kind = thread_kind(key[1])
        ent = kinds.setdefault(kind, {"threads": 0, "cpu_s": 0.0})
        ent["threads"] += 1
        ent["cpu_s"] += d
    python_s = sum(e["cpu_s"] for e in kinds.values())
    process_s = s1["process_s"] - s0["process_s"]
    locks = {}
    for site, b in s1["locks"].items():
        a = s0["locks"].get(site)
        d = {k: b[k] if k.endswith("_max") else b[k] - (a[k] if a else 0)
             for k in b}
        if d["holds"] or d["waits"] or d["sleeps"]:
            locks[site] = d
    return {"window_s": s1["t"] - s0["t"], "thread_kinds": kinds,
            "python_threads_cpu_s": python_s, "process_cpu_s": process_s,
            "native_cpu_s": process_s - python_s, "locks": locks}


# ------------------------------------------------------------------ the run


def run(args) -> dict:
    tree = os.path.abspath(args.tree)
    sys.path.insert(0, tree)
    from benchmark import harness, reference

    cell = harness.load_cell(tree, args.workload)
    for key, value in (("partitions", args.partitions),
                       ("keys_per_partition", args.keys_per_partition)):
        if value:
            cell.config[key] = value
    if args.clients:
        cell.mix = dataclasses.replace(cell.mix, clients=args.clients)
    dep = harness.Deployment(cell, args.seed)
    snaps: list = []
    try:
        dep.open()
        tables = probe_locks(dep.db)
        # the window's bounds are its two counter readings
        # (Deployment.measure: c0 as it opens, c1 as it closes, the
        # last two of the run): a snapshot rides on each
        counters = dep.counters

        def counters_and_snapshot():
            snaps.append(snapshot(tables))
            return counters()

        dep.counters = counters_and_snapshot
        reading = dep.measure(args.seed, args.seconds, False,
                              T_PROCESS_START)
    finally:
        dep.close()
    reduced = harness.reduce_reading(cell, reading, dep.history)
    account = window_account(snaps[-2], snaps[-1])
    answered = sum(reduced["detail"]["answered"].values())
    e2e = reduced["end_to_end"]
    account.update({
        "workload": args.workload, "seed": args.seed, "tree": tree,
        "device": reading["device"],
        "correct": reference.judge(reduced["numbers"]),
        "not_kept": [name for name, *number in reduced["numbers"]
                     if not reference.judge([(name, *number)])],
        "failed": reduced["failed"], "answered": answered,
        "txn_per_s": e2e["txn_per_s"],
        "read_p95_ms": e2e["read_p95_ms"],
        "update_p95_ms": e2e["update_p95_ms"],
        "cpu_ms_per_txn": (1000.0 * account["python_threads_cpu_s"]
                           / answered if answered else None),
        "process_cpu_ms_per_txn": (1000.0 * account["process_cpu_s"]
                                   / answered if answered else None),
    })
    return account


def report(acc: dict) -> str:
    w = acc["window_s"]
    missed = (f" (limits not kept: {', '.join(acc['not_kept'])})"
              if acc["not_kept"] else "")
    lines = [
        f"{acc['workload']} seed {acc['seed']} on "
        f"{acc['device']['platform']} ({acc['device']['kind']}): "
        f"window {w:.2f} s, {acc['answered']} answered "
        f"({acc['txn_per_s']:.1f} txn/s), correct {acc['correct']}"
        f"{missed}, failed {acc['failed']}",
        f"CPU of the Python threads {acc['python_threads_cpu_s']:.2f} s "
        f"({acc['python_threads_cpu_s'] / w:.2f} cores), of the process "
        f"{acc['process_cpu_s']:.2f} s (native threads "
        f"{acc['native_cpu_s']:.2f} s); a transaction: "
        f"{acc['cpu_ms_per_txn']:.3f} CPU-ms of the Python threads, "
        f"{acc['process_cpu_ms_per_txn']:.3f} of the process",
        "", "| thread kind | threads | CPU-s | share |",
        "| --- | --- | --- | --- |"]
    total = acc["python_threads_cpu_s"] or 1.0
    for kind, e in sorted(acc["thread_kinds"].items(),
                          key=lambda kv: -kv[1]["cpu_s"]):
        lines.append(f"| {kind} | {e['threads']} | {e['cpu_s']:.3f} | "
                     f"{100 * e['cpu_s'] / total:.1f} % |")
    lines += ["", "| pm._lock site | holds | held s | mean ms | max ms "
              "| waits | waited s | mean ms | max ms | slept s |",
              "| --- | --- | --- | --- | --- | --- | --- | --- | --- "
              "| --- |"]

    def ms(x):
        return f"{1000 * x:.2f}"

    for site, d in sorted(acc["locks"].items(),
                          key=lambda kv: -kv[1]["wait_s"]):
        lines.append(
            f"| {site} | {d['holds']} | {d['hold_s']:.3f} | "
            f"{ms(d['hold_s'] / d['holds']) if d['holds'] else '-'} | "
            f"{ms(d['hold_max'])} | {d['waits']} | {d['wait_s']:.3f} | "
            f"{ms(d['wait_s'] / d['waits']) if d['waits'] else '-'} | "
            f"{ms(d['wait_max'])} | {d['sleep_s']:.3f} |")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--tree", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="the checkout to import")
    ap.add_argument("--out", help="write the account as JSON here too")
    for name in ("partitions", "keys-per-partition", "clients"):
        ap.add_argument(f"--{name}", type=int, default=0,
                        help="shrink the cell (a rehearsal)")
    args = ap.parse_args(argv)
    acc = run(args)
    print(report(acc), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(acc, f, indent=1)
    print("PROBE " + json.dumps(acc), flush=True)
    return 0 if acc["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
