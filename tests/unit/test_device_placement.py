"""Ring placement of the LIVE data plane over the device mesh
(Config.device_placement="ring"): partition p's materializer state is
committed to chip p % n_devices and every serving-path mutation stays
there — the ring as the live data plane across chips (the reference
instantiates every vnode layer per partition across its nodes,
src/antidote_app.erl:42-59).

Runs on the test env's forced 8-device CPU mesh (conftest)."""

import jax
import pytest

from antidote_tpu.api import AntidoteTPU
from antidote_tpu.config import Config


@pytest.fixture
def placed_db(tmp_path):
    db = AntidoteTPU(config=Config(
        n_partitions=8, data_dir=str(tmp_path),
        device_placement="ring", device_flush_ops=4))
    yield db
    db.close()


def _device_of(plane_state):
    return list(jax.tree_util.tree_leaves(plane_state)[0].devices())[0]


def test_partitions_ring_placed_and_stay_placed(placed_db):
    db = placed_db
    devs = jax.devices()
    assert len(devs) >= 8
    # write enough through the PUBLIC API to force device flushes on
    # every partition (staged rows -> append kernels on each chip)
    tx = db.start_transaction()
    db.update_objects(
        [((k, "counter_pn", "b"), "increment", 1) for k in range(64)]
        + [((k, "set_aw", "b"), "add", b"x") for k in range(100, 164)],
        tx)
    cvc = db.commit_transaction(tx)

    for p, pm in enumerate(db.node.partitions):
        want = devs[p % len(devs)]
        assert pm.device.device == want
        for tn in ("counter_pn", "set_aw"):
            st = pm.device.planes[tn].st
            assert _device_of(st) == want, (p, tn)

    # reads still serve correct values from the placed planes
    tx = db.start_transaction(clock=cvc)
    vals = db.read_objects(
        [(k, "counter_pn", "b") for k in range(64)], tx)
    db.commit_transaction(tx)
    assert vals == [1] * 64


def test_map_subplanes_inherit_placement(placed_db):
    db = placed_db
    devs = jax.devices()
    tx = db.start_transaction()
    db.update_objects(
        [((k, "map_go", "b"), "update",
          (("f", "counter_pn"), ("increment", 3))) for k in range(8)],
        tx)
    cvc = db.commit_transaction(tx)
    tx = db.start_transaction(clock=cvc)
    vals = db.read_objects([(k, "map_go", "b") for k in range(8)], tx)
    db.commit_transaction(tx)
    assert all(v == {("f", "counter_pn"): 3} for v in vals), vals
    for p, pm in enumerate(db.node.partitions):
        mp = pm.device.planes["map_go"]
        for sub in mp._all_planes():
            if getattr(sub, "st", None) is not None and \
                    jax.tree_util.tree_leaves(sub.st):
                assert _device_of(sub.st) == devs[p % len(devs)], p


def test_a_grow_leaves_the_state_on_its_chip(tmp_path, caplog):
    """A capacity grow is a host repack that rebuilds the arrays on the
    DEFAULT device.  Left there, every partition's fold names chip 0
    until the next append moves the state back, and a cross-partition
    read in that window fuses planes of several chips into one program
    — JAX refuses it and the read falls back with an ERROR record
    (found by chip_smoke.py's ring leg after a restart).  Every leaf
    must be back on the plane's chip when the grow returns."""
    import logging

    db = AntidoteTPU(config=Config(
        n_partitions=4, data_dir=str(tmp_path), device_placement="ring",
        device_key_capacity=4, device_async_flush=False))
    try:
        devs = jax.devices()
        # 6 keys per partition: the 5th staged key doubles the capacity
        # of 4, and nothing is appended after it
        keys = [(k, "counter_pn", "b") for k in range(24)]
        cvc = db.update_objects_static(
            None, [(k, "increment", 1) for k in keys])
        for p, pm in enumerate(db.node.partitions):
            plane = pm.device.planes["counter_pn"]
            assert plane.capacity == 8
            placed = {d for leaf in jax.tree_util.tree_leaves(plane.st)
                      for d in leaf.devices()}
            assert placed == {devs[p % len(devs)]}, (p, placed)
            pm._val_cache.clear()  # the fold, not the cache
        with caplog.at_level(logging.ERROR, logger="antidote_tpu"):
            vals, _ = db.read_objects_static(cvc, keys)
        assert vals == [1] * 24
        assert not caplog.records, caplog.records[0].getMessage()
    finally:
        db.close()
