#!/usr/bin/env python3
"""Where one interpreter's CPU goes under a cell's traffic, over a whole
window:

    python3 tools/host_cpu_probe.py --workload bb1dc.update90-uniform \\
        --seed 7 --seconds 15 [--tree <checkout>] [--out probe.json]

brings the cell's deployment up through the benchmark's own harness
(``benchmark/harness.py``: the load, the pattern warm-up, the client
processes, one window, the comparison that decides ``correct``) on
whatever backend JAX finds, and prints what the program's own host
account (``antidote_tpu/obs/host.py``) says of the window:

- the CPU-seconds of the server's Python threads by kind — the wire
  handlers, ``device-flusher``, the rest by name — each thread's own
  clock, and the whole process's beside them (what is left over ran in
  native threads, outside the interpreter);
- CPU-ms a transaction answered;
- every partition's ``pm._lock`` summed by acquiring site: holds,
  waits and the time a site slept on the condition with the lock given
  back;
- the cyclic collector's passes and pauses by generation;
- the device flushes by how long they held the partition lock
  (``antidote_device_flush_split_total{outcome}``) and the waits for a
  flush out of the lock, where the program counts them;
- the durable log's fsyncs, the records they covered, and the
  durability tickets redeemed by what they met
  (``antidote_log_sync_redeemed_total{outcome}``), where the program
  counts them.

The same accounts are the registry's families at ``/metrics``
(``process_cpu_seconds_total``, ``antidote_thread_cpu_seconds_total``,
``antidote_pm_lock_*``, ``antidote_gc_*``); this reads them over the
whole window, untraced.  ``--tree``
names the checkout whose ``antidote_tpu`` and ``benchmark`` are
imported, so one copy of the tool reads a parent and a change; a tree
whose program keeps no host account is refused (read it with this
tool as of commit bab25bb, which instruments the locks and threads
itself).  ``--partitions``, ``--keys-per-partition`` and ``--clients``
shrink the cell for a rehearsal off the chip, where the output is
counts and shares, never rates.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

T_PROCESS_START = time.monotonic()


def run(args) -> dict:
    tree = os.path.abspath(args.tree)
    sys.path.insert(0, tree)
    from benchmark import harness, reference

    try:
        from antidote_tpu.obs import host
    except ImportError:
        raise SystemExit(
            f"{tree}: its program keeps no host account "
            "(antidote_tpu/obs/host.py); read it with "
            "tools/host_cpu_probe.py as of commit bab25bb") from None

    cell = harness.load_cell(tree, args.workload)
    for key, value in (("partitions", args.partitions),
                       ("keys_per_partition", args.keys_per_partition)):
        if value:
            cell.config[key] = value
    if args.clients:
        cell.mix = dataclasses.replace(cell.mix, clients=args.clients)
    dep = harness.Deployment(cell, args.seed)
    snaps: list = []
    flushes: list = []
    logs: list = []
    try:
        dep.open()
        # the window's bounds are its two counter readings
        # (Deployment.measure: as it opens and as it closes, the last
        # two of the run): an account rides on each
        counters = dep.counters

        def counters_and_account():
            snaps.append(host.account())
            flushes.append(flush_account())
            logs.append(log_account())
            return counters()

        dep.counters = counters_and_account
        reading = dep.measure(args.seed, args.seconds, False,
                              T_PROCESS_START)
    finally:
        dep.close()
    reduced = harness.reduce_reading(cell, reading, dep.history)
    account = host.difference(snaps[-2], snaps[-1])
    account["flushes"] = {k: flushes[-1][k] - flushes[-2][k]
                          for k in flushes[-1]}
    account["log"] = {k: logs[-1][k] - logs[-2][k] for k in logs[-1]}
    answered = sum(reduced["detail"]["answered"].values())
    e2e = reduced["end_to_end"]

    def per_txn(seconds):
        return 1000.0 * seconds / answered if answered else None

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
        "cpu_ms_per_txn": per_txn(account["python_threads_cpu_s"]),
        "process_cpu_ms_per_txn": per_txn(account["process_cpu_s"]),
        "pm_lock_hold_ms_per_txn": per_txn(account["pm_lock"]["held_s"]),
        "gc_pause_ms_per_s": (1000.0 * sum(account["gc_pause_s"].values())
                              / account["length_s"]),
    })
    return account


def flush_account() -> dict:
    """The flushes by outcome and the waits for a flush out of the
    lock, so far; empty for a program that does not count them."""
    from antidote_tpu import stats

    reg = stats.registry
    split = getattr(reg, "device_flush_split", None)
    if split is None:
        return {}
    out = {o: split.value(outcome=o)
           for o in ("clean", "overflow", "whole")}
    out["inflight_waits"] = reg.device_flush_inflight_waits.value()
    return out


def log_account() -> dict:
    """The durable log's fsyncs, the records they covered and the
    tickets redeemed by outcome, so far; a program that does not count
    redemptions gives the first two alone."""
    from antidote_tpu import stats

    reg = stats.registry
    out = {"fsyncs": reg.log_fsyncs.value(),
           "records": reg.log_group_records.value()}
    redeemed = getattr(reg, "log_sync_redeemed", None)
    if redeemed is not None:
        for outcome in ("covered", "led", "woken"):
            out[outcome] = redeemed.value(outcome=outcome)
    return out


def report(acc: dict) -> str:
    w = acc["length_s"]
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
        f"{acc['process_cpu_ms_per_txn']:.3f} of the process; "
        f"pm._lock held {acc['pm_lock_hold_ms_per_txn']:.3f} ms; "
        f"the collector {acc['gc_pause_ms_per_s']:.2f} ms/s",
        "collector by generation: " + ", ".join(
            f"{g}: {acc['gc_collections'][g]} passes, "
            f"{acc['gc_pause_s'][g]:.3f} s" for g in sorted(
                acc["gc_pause_s"])),
        "", "| thread kind | CPU-s | share |", "| --- | --- | --- |"]
    fl = acc.get("flushes")
    if fl:
        n = fl["clean"] + fl["overflow"] + fl["whole"]
        lines.insert(-3, (
            f"flushes: clean {fl['clean']:.0f}, overflow "
            f"{fl['overflow']:.0f}, whole {fl['whole']:.0f} (clean "
            f"{100 * fl['clean'] / n if n else 0:.1f} % of {n:.0f}); "
            f"waits on a flush out of the lock {fl['inflight_waits']:.0f}"))
    lg = acc.get("log")
    if lg and lg["fsyncs"]:
        line = (f"durable log: {lg['fsyncs']:.0f} fsyncs, "
                f"{lg['records'] / lg['fsyncs']:.2f} records an fsync")
        if "covered" in lg:
            line += (f"; tickets redeemed: covered {lg['covered']:.0f}, "
                     f"led {lg['led']:.0f}, woken {lg['woken']:.0f}")
        lines.insert(-3, line)
    total = acc["python_threads_cpu_s"] or 1.0
    for kind, s in sorted(acc["thread_cpu_s"].items(),
                          key=lambda kv: -kv[1]):
        if s:
            lines.append(f"| {kind} | {s:.3f} | {100 * s / total:.1f} % |")
    lines += ["", "| pm._lock site | holds | held s | mean ms | waits "
              "| waited s | mean ms | slept s |",
              "| --- | --- | --- | --- | --- | --- | --- | --- |"]

    def mean_ms(total_s, n):
        return f"{1000 * total_s / n:.2f}" if n else "-"

    for site, d in sorted(acc["pm_lock_sites"].items(),
                          key=lambda kv: -kv[1]["waited_s"]):
        lines.append(
            f"| {site} | {d['holds']} | {d['held_s']:.3f} | "
            f"{mean_ms(d['held_s'], d['holds'])} | {d['waits']} | "
            f"{d['waited_s']:.3f} | {mean_ms(d['waited_s'], d['waits'])} "
            f"| {d['slept_s']:.3f} |")
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
