"""Checkpoint + log-truncation plane (ISSUE 10).

The contract under test: recovery from (checkpoint + log suffix) is
bit-identical to recovery from a full log scan, for every key and
CRDT type, on both log backends; a crash at ANY byte of a checkpoint
write leaves a loadable previous state; truncation reclaims log bytes
below the cut without changing any recovered value; and eviction /
read-below-base replay seeds from the checkpoint instead of replaying
from offset 0.
"""

from __future__ import annotations

import os
import threading
import time

import pytest

from antidote_tpu.clocks import VC
from antidote_tpu.config import Config
from antidote_tpu.oplog.checkpoint import (
    CheckpointSettings,
    CheckpointStore,
    ckpt_from_config,
)
from antidote_tpu.oplog.log import GroupSettings
from antidote_tpu.oplog.partition import BelowRetentionFloor, PartitionLog
from antidote_tpu.txn.node import Node

BACKENDS = ("python", "native")


def _mk_cfg(tmp_path, **kw):
    kw.setdefault("device_store", False)
    kw.setdefault("n_partitions", 2)
    kw.setdefault("data_dir", str(tmp_path / "data"))
    return Config(**kw)


def _commit(node, txid_n, updates, certify=False):
    """One committed txn through the real manager path; updates =
    [(key, type_name, effect)] (pre-generated downstream effects)."""
    by_pm = {}
    for key, tn, eff in updates:
        by_pm.setdefault(node.partition_of(key), []).append(
            (key, tn, eff))
    txid = (node.dc_id, txid_n)
    svc = VC({node.dc_id: node.clock.now_us()})
    for pm, ops in by_pm.items():
        for key, tn, eff in ops:
            pm.stage_update(txid, key, tn, eff)
    ct = node.clock.now_us()
    for pm in by_pm:
        pm.prepare(txid, svc, certify=certify)
    for pm in by_pm:
        pm.commit(txid, ct, svc, certified=certify)
    return ct


def _workload(node, n_txns=60, seed=7):
    """Mixed-type committed history: counters, sets (add/rmv with real
    dots via downstream generation), registers — enough shape variety
    to catch a seed/replay mismatch per type."""
    import numpy as np

    from antidote_tpu.crdt import DownstreamCtx, get_type

    rng = np.random.default_rng(seed)
    ctx = DownstreamCtx(mint=node.mint_dot)
    set_cls = get_type("set_aw")
    set_states: dict = {}
    for i in range(n_txns):
        ups = []
        k = int(rng.integers(0, 8))
        ups.append((f"ctr_{k}", "counter_pn", int(rng.integers(1, 9))))
        elem = f"e{int(rng.integers(0, 6))}"
        skey = f"set_{k % 3}"
        st = set_states.setdefault(skey, set_cls.new())
        op = ("add", elem) if (rng.random() < 0.75
                               or elem not in st) else ("remove", elem)
        eff = set_cls.downstream(op, st, ctx)
        set_states[skey] = set_cls.update(eff, st)
        ups.append((skey, "set_aw", eff))
        ups.append((f"reg_{k % 4}", "register_lww",
                    (node.clock.now_us(), (node.dc_id, i), f"v{seed}_{i}")))
        _commit(node, seed * 1_000_000 + i, ups)
    return n_txns


def _all_values(node):
    out = {}
    for pm in node.partitions:
        for key in sorted(pm.log.keys_seen, key=repr):
            tn = ("counter_pn" if key.startswith("ctr_") else
                  "set_aw" if key.startswith("set_") else "register_lww")
            out[key] = pm.value_snapshot(key, tn)
    return out


def _force_ckpt(node):
    for pm in node.partitions:
        assert pm.checkpoint_now() is not None


# --------------------------------------------------------------- store


class TestCheckpointStore:
    def test_roundtrip_and_atomicity(self, tmp_path):
        st = CheckpointStore(str(tmp_path / "p0.ckpt"),
                             CheckpointSettings())
        doc = {"version": 1, "partition": 0, "cut_offset": 10,
               "op_counters": {"dc1": 3}, "max_commit_vc": {},
               "commit_watermarks": {}, "pending": [],
               "pending_floor": 0, "keys": {"k": ("counter_pn", 5, {})},
               "clock": {}, "wall_us": 1}
        st.write_doc(doc)
        assert st.load_doc() == doc
        assert not os.path.exists(st.path + ".tmp")

    def test_truncated_at_every_byte_loads_previous_or_none(
            self, tmp_path):
        """A torn checkpoint file at ANY length must parse as None —
        and since writes go through temp+rename, a crash mid-write
        leaves the PREVIOUS file: simulate both halves."""
        st = CheckpointStore(str(tmp_path / "p0.ckpt"),
                             CheckpointSettings())
        doc = {"version": 1, "partition": 0, "cut_offset": 7,
               "op_counters": {}, "max_commit_vc": {},
               "commit_watermarks": {}, "pending": [],
               "pending_floor": 0, "keys": {}, "clock": {},
               "wall_us": 2}
        st.write_doc(doc)
        with open(st.path, "rb") as f:
            raw = f.read()
        for cut in range(len(raw)):
            torn = CheckpointStore._parse(raw[:cut])
            assert torn is None, f"torn prefix of {cut} bytes parsed"
        # crash BEFORE the rename: stray tmp left behind, previous doc
        # still served
        with open(st.path + ".tmp", "wb") as f:
            f.write(raw[: len(raw) // 2])
        assert st.load_doc() == doc

    def test_unknown_version_loads_none(self, tmp_path):
        st = CheckpointStore(str(tmp_path / "v.ckpt"),
                             CheckpointSettings())
        st.write_doc({"version": 999})
        assert st.load_doc() is None

    def test_factory_routes_config(self):
        cfg = Config(ckpt=False, ckpt_ops=7, ckpt_bytes=9,
                     ckpt_truncate=False, ckpt_retain_ops=3)
        s = ckpt_from_config(cfg)
        assert (s.enabled, s.every_ops, s.every_bytes, s.truncate,
                s.retain_ops) == (False, 7, 9, False, 3)


# ------------------------------------------------- recovery equivalence


@pytest.mark.parametrize("backend", BACKENDS)
def test_ckpt_plus_suffix_equals_full_scan(tmp_path, backend):
    """Every key's recovered value bit-identical between
    (checkpoint + suffix) and (full scan), across CRDT types."""
    from antidote_tpu.oplog import log as oplog_log

    if backend == "native" and oplog_log._NativeBackend.load() is None:
        pytest.skip("no native backend in this environment")
    cfg = _mk_cfg(tmp_path, ckpt=True, ckpt_truncate=False)
    cfg.extra["oplog_backend"] = backend
    node = Node(dc_id="dc1", config=cfg)
    _workload(node, n_txns=50)
    _force_ckpt(node)
    _workload(node, n_txns=25, seed=11)  # the suffix past the cut
    # cut-crossing txn: updates staged before the cut, commit after
    pm = node.partitions[0]
    txid = ("dc1", 99999)
    svc = VC({"dc1": node.clock.now_us()})
    pm.stage_update(txid, "ctr_0", "counter_pn", 100)
    pm.checkpoint_now()  # cut with this txn pending
    pm.commit(txid, node.clock.now_us(), svc, certified=False)
    want = _all_values(node)
    node.close()

    # leg A: checkpoint-seeded recovery (suffix replay only)
    node_a = Node(dc_id="dc1", config=cfg)
    assert any(p.log.suffix_start > 0 for p in node_a.partitions), \
        "checkpoint recovery never engaged"
    got_a = _all_values(node_a)
    node_a.close()
    assert got_a == want

    # leg B: full-scan oracle (checkpoint files deleted; the log was
    # not truncated, so the whole history is still on disk)
    for p in range(cfg.n_partitions):
        os.remove(os.path.join(node.data_dir, f"dc1_p{p}.log.ckpt"))
    node_b = Node(dc_id="dc1", config=cfg)
    assert all(p.log.suffix_start == 0 for p in node_b.partitions)
    got_b = _all_values(node_b)
    node_b.close()
    assert got_b == want


@pytest.mark.parametrize("backend", BACKENDS)
def test_truncated_log_recovers_identically(tmp_path, backend):
    """After truncation the below-cut bytes are GONE, and recovery
    (checkpoint + retained suffix) still reproduces every value."""
    from antidote_tpu.oplog import log as oplog_log

    if backend == "native" and oplog_log._NativeBackend.load() is None:
        pytest.skip("no native backend in this environment")
    cfg = _mk_cfg(tmp_path, ckpt=True, ckpt_truncate=True)
    cfg.extra["oplog_backend"] = backend
    node = Node(dc_id="dc1", config=cfg)
    _workload(node, n_txns=60)
    for pm in node.partitions:
        pm.log.log.flush()  # staged records reach the file for sizing
    sizes_before = [os.path.getsize(pm.log.path)
                    for pm in node.partitions]
    _force_ckpt(node)
    assert any(pm.log.log.truncated_base > 0 for pm in node.partitions)
    sizes_after = [os.path.getsize(pm.log.path)
                   for pm in node.partitions]
    assert sum(sizes_after) < sum(sizes_before), \
        "truncation reclaimed no bytes"
    _workload(node, n_txns=20, seed=23)
    want = _all_values(node)
    node.close()
    re = Node(dc_id="dc1", config=cfg)
    assert _all_values(re) == want
    # op-id watermarks survive: fresh commits continue the dense stream
    _commit(re, 555555, [("ctr_0", "counter_pn", 1)])
    re.close()


def test_crash_mid_checkpoint_recovers_from_previous(tmp_path,
                                                     monkeypatch):
    """A crash mid-checkpoint (rename never happens) leaves the
    previous checkpoint + full suffix — recovery equals the oracle."""
    cfg = _mk_cfg(tmp_path, ckpt=True, ckpt_truncate=False)
    node = Node(dc_id="dc1", config=cfg)
    _workload(node, n_txns=30)
    _force_ckpt(node)
    _workload(node, n_txns=15, seed=3)
    # the "crash": the next checkpoint dies before the atomic rename
    real_replace = os.replace

    def boom(src, dst, *a, **kw):
        if dst.endswith(".ckpt"):
            raise OSError("injected crash mid-checkpoint")
        return real_replace(src, dst, *a, **kw)

    monkeypatch.setattr(os, "replace", boom)
    with pytest.raises(OSError):
        node.partitions[0].checkpoint_now()
    monkeypatch.undo()
    want = _all_values(node)
    node.close()
    re = Node(dc_id="dc1", config=cfg)
    assert _all_values(re) == want
    re.close()


def test_ckpt_off_keeps_legacy_recovery(tmp_path):
    cfg = _mk_cfg(tmp_path, ckpt=False)
    node = Node(dc_id="dc1", config=cfg)
    _workload(node, n_txns=20)
    for pm in node.partitions:
        assert pm.log.ckpt is None
        assert pm.checkpoint_now() is None
        assert not os.path.exists(pm.log.path + ".ckpt")
    want = _all_values(node)
    node.close()
    re = Node(dc_id="dc1", config=cfg)
    assert all(p.log.suffix_start == 0 for p in re.partitions)
    assert _all_values(re) == want
    re.close()


def test_stale_checkpoint_for_vanished_log_is_ignored(tmp_path):
    """A checkpoint whose cut lies beyond the log's end (the log was
    deleted/replaced) must be ignored, not half-applied."""
    cfg = _mk_cfg(tmp_path, ckpt=True, ckpt_truncate=False,
                  n_partitions=1)
    node = Node(dc_id="dc1", config=cfg)
    _workload(node, n_txns=20)
    _force_ckpt(node)
    node.close()
    os.remove(os.path.join(node.data_dir, "dc1_p0.log"))
    re = Node(dc_id="dc1", config=cfg)
    assert re.partitions[0].log.suffix_start == 0
    assert re.partitions[0].log.op_counters == {}
    re.close()


# ------------------------------------------------ watermark-driven writes


def test_op_watermark_triggers_checkpoint(tmp_path):
    cfg = _mk_cfg(tmp_path, ckpt=True, ckpt_ops=20,
                  ckpt_bytes=1 << 40, ckpt_truncate=False,
                  n_partitions=1)
    node = Node(dc_id="dc1", config=cfg)
    for i in range(30):
        _commit(node, i, [("ctr_0", "counter_pn", 1)])
    pm = node.partitions[0]
    assert pm.log.ckpt_doc is not None, \
        "op watermark never triggered a checkpoint"
    assert pm.log.ckpt_doc["keys"]
    node.close()


# --------------------------------------------- seeded replay (evict/read)


def test_evict_replay_seeds_from_checkpoint(tmp_path):
    """After truncation, a key's host migration replays only the log
    SUFFIX on top of the checkpoint seed — and the value is exact."""
    cfg = _mk_cfg(tmp_path, ckpt=True, ckpt_truncate=True,
                  n_partitions=1)
    node = Node(dc_id="dc1", config=cfg)
    for i in range(40):
        _commit(node, i, [("ctr_0", "counter_pn", 1)])
    pm = node.partitions[0]
    pm.checkpoint_now()
    assert pm.log.log.truncated_base > 0
    for i in range(5):
        _commit(node, 100 + i, [("ctr_0", "counter_pn", 1)])
    # the replay source is the seed + suffix: committed_payloads must
    # return ONLY the retained suffix pairs
    suffix = pm.log.committed_payloads(key="ctr_0")
    assert 0 < len(suffix) <= 5
    seed = pm.log.seed_for("ctr_0")
    assert seed is not None and seed[0] == "counter_pn"
    assert seed[1] == 40  # the folded state at the cut
    # and the read path reassembles seed + suffix to the true value
    assert pm.value_snapshot("ctr_0", "counter_pn") == 45
    # a COLD host-store read (entry dropped — the cache-miss log-
    # fallback path) must rebuild from seed + suffix, not suffix alone
    pm._val_cache.clear()
    pm.store._data.pop("ctr_0")
    assert pm.value_snapshot("ctr_0", "counter_pn") == 45
    node.close()


def test_below_floor_raised_after_truncation(tmp_path):
    cfg = _mk_cfg(tmp_path, ckpt=True, ckpt_truncate=True,
                  n_partitions=1)
    node = Node(dc_id="dc1", config=cfg)
    for i in range(30):
        _commit(node, i, [("ctr_0", "counter_pn", 1)])
    pm = node.partitions[0]
    pm.checkpoint_now()
    floor = pm.log.commit_floor.get("dc1", 0)
    assert floor > 0
    with pytest.raises(BelowRetentionFloor) as ei:
        pm.log.committed_txns_in_range("dc1", 1, floor)
    assert ei.value.floor == floor
    # the raw record range guards the same way
    with pytest.raises(BelowRetentionFloor):
        pm.log.records_in_range("dc1", 1, 2)
    # ranges strictly above the floor still serve, with the prev-opid
    # chain seeded from the floor
    for i in range(5):
        _commit(node, 500 + i, [("ctr_0", "counter_pn", 1)])
    got = pm.log.committed_txns_in_range("dc1", floor + 1,
                                         pm.log.op_counters["dc1"])
    assert got and got[0][0] == floor
    node.close()


def test_retention_floor_limits_truncation(tmp_path):
    """A wired retention source (a peer's ship watermark) caps how
    deep truncation reaches: ranges above the floor keep answering."""
    cfg = _mk_cfg(tmp_path, ckpt=True, ckpt_truncate=True,
                  ckpt_retain_ops=0, n_partitions=1)
    node = Node(dc_id="dc1", config=cfg)
    for i in range(30):
        _commit(node, i, [("ctr_0", "counter_pn", 1)])
    pm = node.partitions[0]
    last = pm.log.op_counters["dc1"]
    keep_from = last - 10
    pm.log.retention_opid_source = lambda: keep_from
    pm.checkpoint_now()
    assert pm.log.log.truncated_base > 0
    floor = pm.log.commit_floor.get("dc1", 0)
    assert floor <= keep_from
    got = pm.log.committed_txns_in_range("dc1", keep_from + 1, last)
    assert got
    node.close()
    # the retained (floor, cut] window keeps serving ordinary gap
    # repair AFTER a restart: the hard floor is persisted in the
    # checkpoint, and only ranges reaching below IT bootstrap
    re = Node(dc_id="dc1", config=cfg)
    plog = re.partitions[0].log
    assert plog.suffix_start > 0
    again = plog.committed_txns_in_range("dc1", keep_from + 1, last)
    assert [prev for prev, _r in again] == [prev for prev, _r in got]
    assert [[r.to_bytes() for r in recs] for _p, recs in again] == \
        [[r.to_bytes() for r in recs] for _p, recs in got]
    if floor > 0:
        with pytest.raises(BelowRetentionFloor):
            plog.committed_txns_in_range("dc1", 1, floor)
    re.close()


def test_device_plane_checkpoint_recovery(tmp_path):
    """With the device store ON, checkpoint_now folds device-resident
    keys through the batched per-type fold; after a restart the seeds
    re-install as DEVICE-resident bases (ISSUE 13 — the plane ingests
    the folded state back as rows and folds it into the base at the
    seed frontier), the suffix replays on top, and every value matches
    the pre-restart read — including fresh commits after recovery."""
    cfg = _mk_cfg(tmp_path, device_store=True, ckpt=True,
                  ckpt_truncate=True, n_partitions=1)
    node = Node(dc_id="dc1", config=cfg)
    _workload(node, n_txns=40)
    pm = node.partitions[0]
    doc = pm.checkpoint_now()
    assert doc is not None and doc["keys"]
    # device-owned keys really were folded into the seeds
    dev_keys = [k for k in doc["keys"]
                if pm.device.owns(doc["keys"][k][0], k)]
    assert dev_keys, "no device-resident key reached the checkpoint"
    _workload(node, n_txns=15, seed=29)
    want = _all_values(node)
    node.close()

    re = Node(dc_id="dc1", config=cfg)
    pm2 = re.partitions[0]
    assert pm2.log.suffix_start > 0
    assert _all_values(re) == want
    # seeded keys of ingestable types serve from the DEVICE again —
    # the restarted node re-earned its device economy (pre-ISSUE-13
    # they pinned host_only forever) — and keep working for NEW
    # commits after the restart
    tn_of = {k: doc["keys"][k][0] for k in dev_keys}
    back = [k for k in dev_keys
            if pm2.device.owns(tn_of[k], k)
            and k not in pm2.device.host_only]
    assert back == dev_keys, \
        f"seeded keys stuck host-path: {set(dev_keys) - set(back)}"
    before = pm2.value_snapshot("ctr_0", "counter_pn")
    _commit(re, 777777, [("ctr_0", "counter_pn", 5)])
    assert pm2.value_snapshot("ctr_0", "counter_pn") == before + 5
    re.close()


def test_recovery_replay_flush_keeps_device_ownership(tmp_path):
    """A device flush firing MID-REPLAY (ingest window expiry — the
    parallel-recovery interleaving makes it routine) must not evict
    hot keys: the ring-overflow retry needs a fold horizon, and the
    recovered commit join is a safe one.  A 1µs coalescing window
    forces a flush on every replayed op, overflowing the 8-lane ring
    well before the replay ends — pre-fix, recovery silently demoted
    the key to the host path (values right, device economy gone)."""
    cfg = _mk_cfg(tmp_path, device_store=True, n_partitions=1,
                  ckpt=False, mat_coalesce_us=1,
                  device_async_flush=False)
    node = Node(dc_id="dc1", config=cfg)
    for i in range(3 * cfg.device_lanes):
        _commit(node, i, [("rk", "counter_pn", 1)])
    node.close()
    re = Node(dc_id="dc1", config=cfg)
    pm = re.partitions[0]
    assert pm.value_snapshot("rk", "counter_pn") == 3 * cfg.device_lanes
    assert pm.device.owns("counter_pn", "rk"), \
        "recovery replay evicted a device-resident key"
    re.close()


# --------------------------------------------------- publish ordering


@pytest.mark.parametrize("after", [False, True])
def test_publish_after_durable_ordering(tmp_path, after):
    """Config.publish_after_durable moves _publish behind wait_durable
    (strict durability-before-visibility); default off keeps the
    visibility-first order.  Asserted structurally on the real commit
    path with an instrumented log."""
    cfg = _mk_cfg(tmp_path, sync_log=True, publish_after_durable=after,
                  ckpt=False, n_partitions=1,
                  log_group=True)
    node = Node(dc_id="dc1", config=cfg)
    pm = node.partitions[0]
    order = []
    real_wait = pm.log.wait_durable
    real_publish = pm._publish

    def wait(ticket, txid=None):
        order.append(("wait", ticket is not None))
        return real_wait(ticket, txid=txid)

    def publish(key, tn, payload, stable):
        order.append(("publish", key))
        return real_publish(key, tn, payload, stable)

    pm.log.wait_durable = wait
    pm._publish = publish
    _commit(node, 1, [("k", "counter_pn", 3)])
    kinds = [k for k, _ in order]
    assert "publish" in kinds and "wait" in kinds
    if after:
        assert kinds.index("wait") < kinds.index("publish"), \
            "publish_after_durable=True must gate visibility on the fsync"
    else:
        assert kinds.index("publish") < kinds.index("wait")
    assert pm.value_snapshot("k", "counter_pn") == 3
    node.close()


def test_ckpt_cut_waits_out_deferred_publish(tmp_path):
    """A checkpoint cut taken inside the publish_after_durable window
    (commit record appended, effects not yet published) would put the
    commit BELOW the cut while the seed fold misses its effect — the
    durable, acked txn would vanish from both seed and suffix on
    recovery.  checkpoint_now must quiesce in-flight deferred
    publishes before capturing the cut (pre-fix: recovered value 3,
    the deferred +4 lost)."""
    cfg = _mk_cfg(tmp_path, sync_log=True, publish_after_durable=True,
                  ckpt=True, ckpt_ops=1 << 30, ckpt_bytes=1 << 40,
                  n_partitions=1, log_group=True)
    node = Node(dc_id="dc1", config=cfg)
    pm = node.partitions[0]
    _commit(node, 1, [("dk", "counter_pn", 3)])  # published + durable
    gate = threading.Event()
    fsync_entered = threading.Event()
    real_sync = pm.log.log._backend_sync

    def slow_sync(io):
        fsync_entered.set()
        gate.wait(5.0)
        return real_sync(io)

    pm.log.log._backend_sync = slow_sync
    committer = threading.Thread(
        target=lambda: _commit(node, 2, [("dk", "counter_pn", 4)]))
    committer.start()
    assert fsync_entered.wait(5.0)
    # commit record is in the log, publish deferred behind the wedged
    # fsync: a checkpoint fired NOW must not cut past it
    docs = []
    ckpt = threading.Thread(
        target=lambda: docs.append(pm.checkpoint_now()))
    ckpt.start()
    time.sleep(0.1)
    assert ckpt.is_alive(), \
        "checkpoint_now cut inside the deferred-publish window"
    gate.set()
    committer.join(5.0)
    ckpt.join(5.0)
    assert not committer.is_alive() and not ckpt.is_alive()
    assert docs and docs[0] is not None
    node.close()
    re = Node(dc_id="dc1", config=cfg)
    assert re.partitions[0].value_snapshot("dk", "counter_pn") == 7, \
        "deferred-publish commit lost below the checkpoint cut"
    re.close()


def test_publish_after_durable_not_visible_before_fsync(tmp_path):
    """With an injected slow fsync, the materializer plane must keep
    serving the PREVIOUS value until the durability ticket is covered
    (the key frontier / warm cache only advance at publish time)."""
    cfg = _mk_cfg(tmp_path, sync_log=True, publish_after_durable=True,
                  ckpt=False, n_partitions=1, log_group=True)
    node = Node(dc_id="dc1", config=cfg)
    pm = node.partitions[0]
    _commit(node, 1, [("k2", "counter_pn", 3)])  # published + durable
    gate = threading.Event()
    fsync_entered = threading.Event()
    real_sync = pm.log.log._backend_sync

    def slow_sync(io):
        fsync_entered.set()
        gate.wait(5.0)
        return real_sync(io)

    pm.log.log._backend_sync = slow_sync
    t = threading.Thread(
        target=lambda: _commit(node, 2, [("k2", "counter_pn", 4)]))
    t.start()
    assert fsync_entered.wait(5.0)
    # the fsync is in flight and publish deferred behind it: the
    # frontier has not moved, so the plane still serves the old value
    time.sleep(0.05)
    assert pm.value_snapshot("k2", "counter_pn") == 3
    gate.set()
    t.join(5.0)
    assert not t.is_alive()
    assert pm.value_snapshot("k2", "counter_pn") == 7
    node.close()


# --------------------------------------------------- truncation mechanics


@pytest.mark.parametrize("backend", BACKENDS)
def test_durable_log_truncate_below_keeps_logical_offsets(
        tmp_path, backend):
    from antidote_tpu.oplog import log as oplog_log
    from antidote_tpu.oplog.log import DurableLog

    if backend == "native" and oplog_log._NativeBackend.load() is None:
        pytest.skip("no native backend in this environment")
    lg = DurableLog(str(tmp_path / "t.log"), backend=backend,
                    group=GroupSettings(enabled=True))
    offs = [lg.append(f"rec{i}".encode() * 4) for i in range(20)]
    lg.flush()
    cut = offs[12]
    end = lg.end_offset()
    lg.truncate_below(cut)
    assert lg.truncated_base == cut
    assert lg.end_offset() == end
    for off in offs[:12]:
        assert lg.read(off) is None
    for i, off in enumerate(offs[12:], start=12):
        assert lg.read(off) == f"rec{i}".encode() * 4
    # scans clamp to the base; appends continue the logical stream
    assert [o for o, _p in lg.scan(0)] == offs[12:]
    off_new = lg.append(b"after-truncate")
    assert off_new == end
    lg.flush()
    assert lg.read(off_new) == b"after-truncate"
    lg.close()
    # a REOPEN parses the truncation marker and keeps every offset
    re = DurableLog(str(tmp_path / "t.log"), backend=backend)
    assert re.truncated_base == cut
    assert re.read(offs[11]) is None
    assert re.read(offs[15]) == b"rec15" * 4
    assert re.read(off_new) == b"after-truncate"
    assert re.end_offset() == end + len(b"after-truncate") + 8
    re.close()


def test_truncate_below_is_idempotent_and_monotone(tmp_path):
    from antidote_tpu.oplog.log import DurableLog

    lg = DurableLog(str(tmp_path / "m.log"), backend="python")
    offs = [lg.append(b"x" * 10) for _ in range(10)]
    lg.truncate_below(offs[4])
    lg.truncate_below(offs[2])  # below the base: no-op
    assert lg.truncated_base == offs[4]
    lg.truncate_below(offs[7])
    assert lg.truncated_base == offs[7]
    assert lg.read(offs[7]) == b"x" * 10
    lg.close()


@pytest.mark.parametrize("group", [False, True])
def test_log_stats_retained_bytes_tracks_growth(tmp_path, group):
    """log_stats must report live end/retained_bytes on BOTH log
    paths: queue_stats()['end'] is the group plane's watermark and
    stays frozen at its boot value under Config.log_group=False
    (pre-fix the growth gauges never moved there)."""
    cfg = _mk_cfg(tmp_path, n_partitions=1, ckpt=False,
                  log_group=group)
    node = Node(dc_id="dc1", config=cfg)
    pm = node.partitions[0]
    before = pm.log.log_stats()["retained_bytes"]
    _workload(node, n_txns=20)
    after = pm.log.log_stats()["retained_bytes"]
    assert after > before, \
        f"retained_bytes frozen under log_group={group}"
    node.close()


def test_post_restart_truncation_floors_cover_blind_window(tmp_path):
    """After a checkpoint-seeded restart the rebuilt index is blind
    below the boot cut; a truncation reclaiming those bytes must push
    the repair floors to the cut watermarks anyway.  The hole needs an
    origin with NO suffix records (a monotone origin's suffix commits
    raise its floor past the blind opids as a side effect): pre-fix,
    the floors came from the (suffix-only) index, so that origin's
    floor never rose and a repair read into the reclaimed window
    silently answered [] instead of BELOW_FLOOR — the requester treats
    an empty answer as authoritative absence, a permanent hole."""
    from antidote_tpu.interdc import query as idc_query
    from antidote_tpu.oplog.records import (
        OpId,
        commit_record,
        update_record,
    )

    cfg1 = _mk_cfg(tmp_path, n_partitions=1, ckpt=True,
                   ckpt_truncate=False, ckpt_ops=1 << 30,
                   ckpt_bytes=1 << 40)
    node = Node(dc_id="dc1", config=cfg1)
    pm = node.partitions[0]
    for i in range(10):
        _commit(node, i, [("bw", "counter_pn", 1)])
    for i in range(8):  # a remote origin, then it goes quiet forever
        txid = ("dcR", i)
        vc = VC({"dcR": 1000 + i})
        pm.apply_remote(
            [update_record(OpId("dcR", 2 * i + 1), txid, "bw_r",
                           "counter_pn", 1),
             commit_record(OpId("dcR", 2 * i + 2), txid, "dcR",
                           1000 + i, vc)],
            "dcR", 1000 + i, vc)
    pm.checkpoint_now()  # cut C > 0, nothing truncated
    wm_r = pm.log.ckpt_doc["commit_watermarks"]["dcR"]
    assert pm.log.log.truncated_base == 0 and wm_r == 16
    node.close()

    cfg2 = _mk_cfg(tmp_path, n_partitions=1, ckpt=True,
                   ckpt_truncate=True, ckpt_retain_ops=0,
                   ckpt_ops=1 << 30, ckpt_bytes=1 << 40)
    node2 = Node(dc_id="dc1", config=cfg2)
    pm2 = node2.partitions[0]
    assert pm2.log.suffix_start > 0  # index blind below the boot cut
    for i in range(5):  # suffix holds LOCAL records only
        _commit(node2, 100 + i, [("bw", "counter_pn", 1)])
    pm2.checkpoint_now()  # reclaims the blind window
    assert pm2.log.log.truncated_base > 0
    assert pm2.log.commit_floor.get("dcR", 0) >= wm_r, \
        "truncation floors under-raised over the index-blind window"
    ans = pm2.scan_log(lambda lg: idc_query.answer_log_read(
        lg, "dcR", 0, 1, wm_r))
    assert idc_query.is_below_floor(ans), \
        "repair read into the reclaimed blind window did not escalate"
    assert pm2.value_snapshot("bw", "counter_pn") == 15
    assert pm2.value_snapshot("bw_r", "counter_pn") == 8
    node2.close()


def test_repartition_over_truncated_log_seeds_from_checkpoint(tmp_path):
    """ISSUE 19 flips the pre-ISSUE-19 refusal: a truncated log no
    longer blocks a resize — the fold seeds each slot from its
    checkpoint cut and replays only the suffix, so no below-cut op is
    lost.  With Config.resize_from_ckpt off the loud refusal stays
    (a full-history fold over reclaimed bytes would silently lose
    them)."""
    cfg = _mk_cfg(tmp_path, ckpt=True, ckpt_truncate=True,
                  n_partitions=2)
    node = Node(dc_id="dc1", config=cfg)
    _workload(node, n_txns=30)
    _force_ckpt(node)
    assert any(pm.log.log.truncated_base > 0 for pm in node.partitions)
    before = _all_values(node)
    cfg.resize_from_ckpt = False
    with pytest.raises(RuntimeError, match="truncated"):
        node.repartition(4)
    assert len(node.partitions) == 2, "refused resize mutated the ring"
    assert _all_values(node) == before, "refused resize mutated state"
    cfg.resize_from_ckpt = True
    node.repartition(4)
    assert len(node.partitions) == 4
    assert all(pm.log.renumbered for pm in node.partitions), \
        "seeded fold must mark every re-cut log renumbered"
    assert _all_values(node) == before, \
        "seeded resize changed recovered values"
    node.close()
    # the re-cut checkpoint + suffix must survive a cold restart
    node2 = Node(dc_id="dc1", config=cfg)
    assert _all_values(node2) == before, \
        "seeded resize state lost across restart"
    node2.close()


# --------------------- commit concurrency during truncation (ISSUE 11)


@pytest.mark.parametrize("backend", BACKENDS)
def test_commit_lands_during_truncation_tail_copy(tmp_path, backend,
                                                  monkeypatch):
    """The ROADMAP remainder this PR resolves: the retained-suffix
    tail copy (possibly hundreds of MB held back by the retention
    floor) stages OUTSIDE the partition lock.  Park the stage copy
    mid-flight, prove a commit completes immediately (pre-ISSUE-11 it
    stalled behind the lock for the whole copy), then prove the
    commit's bytes survive the rename via the bounded under-lock
    catch-up — recovery after restart still sees them."""
    from antidote_tpu.oplog import log as oplog_log

    if backend == "native" and oplog_log._NativeBackend.load() is None:
        pytest.skip("no native backend in this environment")
    cfg = _mk_cfg(tmp_path, ckpt=True, ckpt_truncate=True,
                  n_partitions=1)
    cfg.extra["oplog_backend"] = backend
    node = Node(dc_id="dc1", config=cfg)
    _workload(node, n_txns=60)
    pm = node.partitions[0]

    staging = threading.Event()
    committed = threading.Event()
    real_copy = oplog_log._copy_range

    def gated_copy(src, dst, nbytes, chunk=1 << 20):
        # park only the out-of-lock stage copy (the first call); the
        # under-lock catch-up copy runs after `committed` is set and
        # passes straight through
        if not staging.is_set():
            staging.set()
            committed.wait(timeout=30)
        return real_copy(src, dst, nbytes, chunk)

    monkeypatch.setattr(oplog_log, "_copy_range", gated_copy)

    ckpt_err = []

    def run_ckpt():
        try:
            assert pm.checkpoint_now() is not None
        except BaseException as e:  # surfaced after join
            ckpt_err.append(e)

    t = threading.Thread(target=run_ckpt)
    t.start()
    try:
        assert staging.wait(timeout=30), "truncation never staged"
        # reads don't stall behind the parked copy either
        v0 = pm.value_snapshot("ctr_0", "counter_pn")
        t0 = time.monotonic()
        _commit(node, 777777, [("ctr_0", "counter_pn", 100)])
        commit_s = time.monotonic() - t0
    finally:
        committed.set()
    t.join(timeout=60)
    assert not t.is_alive(), "checkpoint wedged"
    assert not ckpt_err, ckpt_err
    assert commit_s < 10, \
        f"commit stalled {commit_s:.1f}s behind the tail copy"
    assert pm.log.log.truncated_base > 0
    want = _all_values(node)
    assert want["ctr_0"] == v0 + 100
    node.close()

    # the during-copy commit is PAST the cut, so recovery must replay
    # it from the retained log suffix: a lost catch-up (bytes left on
    # the unlinked pre-rename inode) shows up as a value mismatch here
    re = Node(dc_id="dc1", config=cfg)
    got = _all_values(re)
    re.close()
    assert got == want


# ------------------------------------- the fold outside the lock (ISSUE 32)


def test_key_evicted_by_the_captures_flush_takes_the_host_path(tmp_path):
    """The capture flushes a plane's staged rows for the dirty keys,
    and a flush can evict (here: six unstable ops on a two-lane key).
    Ownership is checked again after it: the evicted key's seed is
    read from the host store under the same hold, the key that stayed
    is folded on the device, and both are exact after a restart."""
    cfg = _mk_cfg(tmp_path, device_store=True, ckpt=True, n_partitions=1,
                  ckpt_ops=1 << 30, ckpt_bytes=1 << 40, device_lanes=2)
    node = Node(dc_id="dc1", config=cfg)
    pm = node.partitions[0]
    for i in range(6):
        _commit(node, 200 + i, [("ctr_0", "counter_pn", 1)], certify=True)
    for i in range(2):
        _commit(node, 300 + i, [("ctr_1", "counter_pn", 1)], certify=True)
    assert pm.device.owns("counter_pn", "ctr_0") \
        and pm.device.owns("counter_pn", "ctr_1")
    frontiers = {k: dict(pm.key_frontier[k]) for k in ("ctr_0", "ctr_1")}
    doc = pm.checkpoint_now()
    assert not pm.device.owns("counter_pn", "ctr_0"), \
        "the capture's flush evicted nothing: the case is not set up"
    assert pm.device.owns("counter_pn", "ctr_1")
    assert doc["keys"]["ctr_0"] == ("counter_pn", 6, frontiers["ctr_0"])
    assert doc["keys"]["ctr_1"] == ("counter_pn", 2, frontiers["ctr_1"])
    assert pm._dev_readers == 0
    _commit(node, 400, [("ctr_0", "counter_pn", 10)], certify=True)
    want = _all_values(node)
    assert want["ctr_0"] == 16
    node.close()
    re = Node(dc_id="dc1", config=cfg)
    assert _all_values(re) == want
    re.close()


@pytest.mark.parametrize("readers,sync_log", [
    pytest.param(0, False, id="0"), pytest.param(3, False, id="3"),
    pytest.param(3, True, id="3-sync_log")])
def test_checkpoints_beside_commits_lose_and_double_nothing(
        tmp_path, readers, sync_log):
    """Stress (time-bounded): eight committers, a counter each, beside
    one thread that cuts checkpoint after checkpoint — and, in the
    second case, three readers whose reads reach the planes (a value
    cache of one entry) — the interpreter switching threads every
    10 us.  Every step is in the live value once; no reader count is
    left behind; and a restart from the last document plus its suffix
    reads the same values — a step folded into a seed AND left above
    its cut would read twice, one dropped from both never.  What it
    guards: a publish waits for device readers with the partition lock
    released, the fold's own count among them since ISSUE 32, so a
    commit record must not be in the log across that wait (commit()
    waits first): a cut taken then lost the step at the key's next
    eviction, and a host-store miss rebuilt from the log then applied
    it twice (the parent: every run with readers).  (One writer a key:
    two uncertified writers of one key can publish against their
    commit times' order, which a seed's clock cannot tell from
    "already folded", fold or no fold.)  Under ``sync_log`` (the
    default order: publish, then wait for the fsync) a cut may fall
    between a commit's publish and its acknowledgement: the record is
    then below the cut and its effect in the seed, whether or not its
    committer has been told."""
    import sys

    cfg = _mk_cfg(tmp_path, device_store=True, ckpt=True, n_partitions=1,
                  ckpt_ops=1 << 30, ckpt_bytes=1 << 40, sync_log=sync_log)
    node = Node(dc_id="dc1", config=cfg)
    pm = node.partitions[0]
    pm._val_cache_cap = 1
    keys = [f"ctr_{i}" for i in range(8)]
    stop_at = time.monotonic() + 2.0
    cuts, steps, errors = [], [0] * 8, []

    def reader():
        try:
            while time.monotonic() < stop_at:
                pm.read_many([(k, "counter_pn") for k in keys], None)
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    def committer(i):
        try:
            while time.monotonic() < stop_at:
                # the commit time is drawn at the prepare, as a served
                # transaction's is (_commit draws it before: one at a
                # time)
                txid = ("dc1", 40_000_000 + i * 100_000 + steps[i])
                pm.stage_update(txid, keys[i], "counter_pn", 1)
                pm.single_commit(txid, VC({"dc1": node.clock.now_us()}))
                steps[i] += 1
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    def cutter():
        try:
            while time.monotonic() < stop_at:
                cuts.append(pm.checkpoint_now()["cut_offset"])
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=committer, args=(i,))
                   for i in range(8)] + [threading.Thread(target=cutter)] \
            + [threading.Thread(target=reader) for _ in range(readers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
            assert not t.is_alive(), "a thread of the stress wedged"
    finally:
        sys.setswitchinterval(interval)
    assert not errors, errors
    assert len(cuts) > 2 and cuts == sorted(cuts)
    assert pm._dev_readers == 0 and not pm._ckpt_inflight
    assert sum(steps) > 50
    want = dict(zip(keys, steps))
    assert {k: pm.value_snapshot(k, "counter_pn") for k in keys} == want
    node.close()
    re = Node(dc_id="dc1", config=cfg)
    pm2 = re.partitions[0]
    assert pm2.log.suffix_start == cuts[-1]
    assert {k: pm2.value_snapshot(k, "counter_pn") for k in keys} == want
    re.close()
