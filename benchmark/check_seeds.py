#!/usr/bin/env python3
"""Many seeds against one loaded deployment: the readings the limits of
``correct`` are set from.

    python3 benchmark/check_seeds.py --workload <name> --seeds 1,2,3 --seconds 6

One set-up (the load is most of a run), then for every seed fresh
clients, a short warm-up, a window at the cell's own load, the read-back
and the comparison — the program's numbers (the lower readings) and the
control's (the upper ones): the reference put in the program's place
with one stated guarantee broken (``reference.control_numbers``; a
record answered one field one write late).  It takes any configuration
``load_cell`` takes, record types included, with the keyspace and the
reference a run builds.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None, root: str = ROOT) -> int:
    from benchmark import harness, reference, run

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    cell = harness.load_cell(root, args.workload)
    run.find_chip(cell.chips)
    dep = harness.Deployment(cell, seeds[0])
    all_correct = True
    try:
        dep.open()
        for seed in seeds:
            reading = dep.measure(seed, args.seconds, bool(args.trace),
                                  T_PROCESS_START, warm_max_s=10.0)
            reduced = harness.reduce_reading(cell, reading, dep.history)
            program = {n: v for n, v, _c, _l in reduced["numbers"]}
            control = reference.control_numbers(
                dep.history, reading["records"], reading["readback"])
            correct = reference.judge(reduced["numbers"])
            all_correct &= correct
            metrics = {k: v for k, v in {
                **reduced["end_to_end"], **reduced["per_layer"]}.items()
                if v is not None and k != "setup_s"}
            print("SEED " + json.dumps({
                "seed": seed, "correct": correct, "program": program,
                "control": control, "metrics": metrics,
                "detail": reduced["detail"],
                "device": reading["device"]}), flush=True)
    finally:
        dep.close()
    return 0 if all_correct else 1


if __name__ == "__main__":
    sys.exit(main())
