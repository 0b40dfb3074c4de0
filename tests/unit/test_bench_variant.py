"""The headline sweep's shapes, seeds and per-variant contract
(bench.py)."""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import bench  # noqa: E402


def test_sweep_shapes():
    sweep = bench.headline_sweep(20)
    assert sweep["b1"][:3] == (1, 4, 20)
    assert sweep["b4"][:3] == (4, 3, 5)
    assert sweep["b8"][:3] == (8, 2, 2)
    # exactly one variant carries the read measurements
    assert sum(1 for v in sweep.values() if v[3]) == 1
    # quick mode keeps every variant runnable
    for c, g, n, _r, _s in bench.headline_sweep(4).values():
        assert n >= 2 and g >= 1


def test_sweep_seeds_deterministic_and_distinct():
    """A variant's rng comes from the sweep's per-variant seed — the
    seed must be stable across calls (or a variant's stream depends on
    the run length) and distinct per variant (or coalescing levels
    replay the same ops and the comparison degenerates)."""
    a = bench.headline_sweep(20)
    b = bench.headline_sweep(4)
    seeds_a = {name: v[4] for name, v in a.items()}
    seeds_b = {name: v[4] for name, v in b.items()}
    assert seeds_a == seeds_b  # n_steps must not perturb the seed
    assert len(set(seeds_a.values())) == len(seeds_a)
    # b1 keeps the historic stream (a fresh rng(0) is what the old
    # thread-through handed it)
    assert seeds_a["b1"] == 0


def test_bench_variant_contract():
    rng = np.random.default_rng(0)
    v, stc, frontier, fetch_oh = bench.bench_variant(
        16_384, 2_048, 8, 3, 1, rng, coalesce=2, gc_every_v=2,
        n_appends=2)
    assert v["ops_per_sec"] > 0
    assert v["batch_rows"] == 4_096
    assert v["ops"] == 4_096 * 2 - v["overflow_dropped"]
    assert stc.dots.shape[0] == 16_384
    assert fetch_oh >= 0
