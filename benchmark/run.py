#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Last stdout line: one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics`` and ``device`` (and in a traced run
``breakdown``), then the numbers compared, each beside its limit.
Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result; nothing falls back to a CPU.
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


def find_chip(chips: int) -> None:
    """Exit 2 unless JAX holds a TPU with the chips the cell asks for.
    Tests that rehearse the rest of a run on the CPU replace this
    function; the program has no switch for it."""
    import jax

    if jax.default_backend() != "tpu":
        print(f"benchmark: no TPU (JAX's backend is "
              f"{jax.default_backend()!r}); the benchmark does not run "
              "on the CPU", file=sys.stderr)
        raise SystemExit(2)
    if len(jax.devices()) < chips:
        print(f"benchmark: the cell asks for {chips} chip(s), JAX finds "
              f"{len(jax.devices())}", file=sys.stderr)
        raise SystemExit(2)


def main(argv=None, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark import harness, reference

    cell = harness.load_cell(root, args.workload)
    find_chip(cell.chips)
    traced = bool(args.trace)
    dep = harness.Deployment(cell, args.seed)
    try:
        dep.open()
        reading = dep.measure(args.seed, args.seconds, traced,
                              T_PROCESS_START)
    finally:
        dep.close()
    reduced = harness.reduce_reading(cell, reading, dep.history)
    line = harness.result_line(cell, traced, reduced, reading["device"],
                               reading["trace"])
    print("DETAIL " + json.dumps(reduced["detail"]), flush=True)
    print(json.dumps(line), flush=True)
    for text in reference.compared_lines(reduced["numbers"])[0]:
        print(text, file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
