"""The GC horizon is strictly below min-prepared.

A single-partition transaction commits at its own prepare time, so the
node's min-prepared time EQUALS the commit time of the transaction that
is publishing its effects.  A fold at that (inclusive) horizon takes the
effects published so far into the base at the commit time, and the rest
of the same transaction's effects then count as already covered: the
host store drops them for good, the device plane until its next fold.
Found by chip_smoke.py's Zipfian write round, where a hot counter read
back hundreds short.  Each test makes every commit sample a fresh
horizon and folds in the middle of one transaction's publish, so the
loss is deterministic without the fix (txn/manager.py _stable_for_gc)."""

import pytest

from antidote_tpu.api import AntidoteTPU
from antidote_tpu.config import Config
from antidote_tpu.txn import manager


@pytest.fixture
def fresh_horizon(monkeypatch):
    monkeypatch.setattr(manager, "_STABLE_REFRESH_S", -1.0)


def test_host_store_fold_keeps_the_rest_of_the_transaction(
        tmp_path, fresh_horizon):
    """The host store folds a key at its 50th op: ops 51..60 of the
    same transaction must survive it."""
    db = AntidoteTPU(config=Config(
        n_partitions=1, data_dir=str(tmp_path), stable_ttl_s=0.0,
        device_store=False))
    try:
        key = ("hot", "counter_pn", "b")
        clock = db.update_objects_static(
            None, [(key, "increment", i + 1) for i in range(60)])
        values, _ = db.read_objects_static(clock, [key])
        assert values == [sum(range(1, 61))]
    finally:
        db.close()


def test_device_fold_keeps_the_rest_of_the_transaction(
        tmp_path, fresh_horizon):
    """The plane flushes and folds after 30 staged effects: the other
    15 of the same transaction land in the ring afterwards and must be
    read, not masked as covered by the base."""
    db = AntidoteTPU(config=Config(
        n_partitions=1, data_dir=str(tmp_path), stable_ttl_s=0.0,
        device_flush_ops=30, device_gc_ops=30, mat_coalesce_us=0,
        device_async_flush=False))
    try:
        keys = [(f"k{i}", "counter_pn", "b") for i in range(45)]
        clock = db.update_objects_static(
            None, [(k, "increment", i + 1) for i, k in enumerate(keys)])
        # the value cache was seeded by the writes: the fold is what
        # is under test
        db.node.partitions[0]._val_cache.clear()
        values, _ = db.read_objects_static(clock, keys)
        assert values == [i + 1 for i in range(45)]
    finally:
        db.close()
