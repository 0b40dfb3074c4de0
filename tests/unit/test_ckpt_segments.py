"""Segmented checkpoint engine (ISSUE 13).

The contract under test: a watermark checkpoint persists ONLY the
dirty delta (a segment) + a small manifest, recovery merges segments
newest-entry-wins and is bit-identical to both the monolithic
document and the full scan; a torn or missing segment refuses the
WHOLE checkpoint loudly (never a silent half-keyspace); compaction is
crash-safe (the old manifest stays authoritative until the new one's
rename) and single-flight against concurrent checkpoints; the
``ckpt_segmented=False`` knob keeps the PR-9 one-document form
bit-for-bit; and device-plane seed re-ingestion round-trips every
supported type's folded state exactly.
"""

from __future__ import annotations

import glob
import os
import threading

import pytest

from antidote_tpu.clocks import VC
from antidote_tpu.config import Config
from antidote_tpu.oplog.checkpoint import (
    CheckpointStore,
    _parse_segment_bytes,
    ckpt_from_config,
    delete_checkpoint_files,
    segment_glob,
)
from antidote_tpu.txn.node import Node

from tests.unit.test_checkpoint import (
    _all_values,
    _commit,
    _mk_cfg,
    _workload,
)


def _segfiles(node):
    out = []
    for pm in node.partitions:
        out.extend(segment_glob(pm.log.path + ".ckpt"))
    return out


def _mk(tmp_path, **kw):
    kw.setdefault("n_partitions", 1)
    kw.setdefault("ckpt_truncate", False)
    kw.setdefault("ckpt_ops", 1 << 30)
    kw.setdefault("ckpt_bytes", 1 << 40)
    return _mk_cfg(tmp_path, **kw)


# ----------------------------------------------------- knob + factory


def test_factory_routes_segment_knobs():
    cfg = Config(ckpt_segmented=False, ckpt_seg_waste_frac=0.25)
    s = ckpt_from_config(cfg)
    assert (s.segmented, s.seg_waste_frac) == (False, 0.25)
    assert ckpt_from_config(None).segmented is True


def test_monolithic_knob_keeps_one_document_form(tmp_path):
    """ckpt_segmented=False writes the PR-9 shape exactly: keys
    inline in the document, no segment files, no segmented fields."""
    cfg = _mk(tmp_path, ckpt_segmented=False)
    node = Node(dc_id="dc1", config=cfg)
    _workload(node, n_txns=20)
    pm = node.partitions[0]
    assert pm.checkpoint_now() is not None
    assert _segfiles(node) == []
    store = pm.log.ckpt
    raw_doc = CheckpointStore._parse(
        open(store.path, "rb").read())
    assert raw_doc is not None
    assert "segments" not in raw_doc and "delta" not in raw_doc \
        and "prev_segments" not in raw_doc
    assert raw_doc["keys"], "monolithic doc must inline the seeds"
    node.close()


def test_segmented_recovery_equals_monolithic_and_full_scan(tmp_path):
    """Same workload, three recoveries — segmented, monolithic, full
    scan — all bit-identical (the knob changes cost, never content)."""
    import shutil

    cfg = _mk(tmp_path, ckpt_segmented=True)
    node = Node(dc_id="dc1", config=cfg)
    _workload(node, n_txns=40)
    pm = node.partitions[0]
    assert pm.checkpoint_now() is not None
    _workload(node, n_txns=10, seed=23)  # a suffix past the cut
    want = _all_values(node)
    node.close()
    assert _segfiles_dir(cfg.data_dir)

    re = Node(dc_id="dc1", config=cfg)
    assert re.partitions[0].log.suffix_start > 0
    assert _all_values(re) == want
    re.close()

    mono_dir = str(tmp_path / "mono")
    shutil.copytree(cfg.data_dir, mono_dir)
    mono = Node(dc_id="dc1", config=_mk(
        tmp_path, ckpt_segmented=False, data_dir=mono_dir))
    # loading follows the on-disk document's shape, knob or not
    assert mono.partitions[0].log.suffix_start > 0
    assert _all_values(mono) == want
    mono.close()

    scan_dir = str(tmp_path / "scan")
    shutil.copytree(cfg.data_dir, scan_dir)
    for f in os.listdir(scan_dir):
        if f.endswith(".ckpt"):
            delete_checkpoint_files(os.path.join(scan_dir, f))
    scan = Node(dc_id="dc1", config=_mk(
        tmp_path, ckpt=False, data_dir=scan_dir))
    assert _all_values(scan) == want
    scan.close()


def _segfiles_dir(data_dir):
    return sorted(glob.glob(os.path.join(data_dir, "*.ckpt.seg-*")))


# ------------------------------------------------- churn proportional


def test_second_cut_persists_only_the_dirty_delta(tmp_path):
    """The O(churn) contract, structurally: after a base cut over N
    keys, a cut with ONE dirty key writes a segment holding exactly
    that key."""
    cfg = _mk(tmp_path)
    node = Node(dc_id="dc1", config=cfg)
    for i in range(24):
        _commit(node, i, [(f"ctr_{i}", "counter_pn", 1)])
    pm = node.partitions[0]
    assert pm.checkpoint_now() is not None
    before = _segfiles(node)
    assert len(before) == 1
    _commit(node, 1000, [("ctr_3", "counter_pn", 5)])
    assert pm.checkpoint_now() is not None
    after = _segfiles(node)
    new = [p for p in after if p not in before]
    assert len(new) == 1
    with open(new[0], "rb") as f:
        entries = _parse_segment_bytes(f.read())
    assert set(entries) == {"ctr_3"}, \
        f"dirty-delta segment carried {set(entries)}"
    # the manifest still merges the full seed set
    assert len(pm.log.ckpt_seeds) == 24
    node.close()


def test_compaction_folds_segments_and_counts(tmp_path):
    """Re-folding the same keys accumulates superseded entries; past
    the waste fraction the next cut compacts to ONE segment and the
    merged content is unchanged."""
    from antidote_tpu import stats

    cfg = _mk(tmp_path, ckpt_seg_waste_frac=0.4)
    node = Node(dc_id="dc1", config=cfg)
    for i in range(8):
        _commit(node, i, [(f"ctr_{i}", "counter_pn", 1)])
    pm = node.partitions[0]
    assert pm.checkpoint_now() is not None
    before_compactions = stats.registry.ckpt_seg_compactions.value()
    n = 100
    for _round in range(4):
        for i in range(8):
            _commit(node, n, [(f"ctr_{i}", "counter_pn", 1)])
            n += 1
        assert pm.checkpoint_now() is not None
    assert stats.registry.ckpt_seg_compactions.value() \
        > before_compactions
    assert len(_segfiles(node)) <= 2, \
        "compaction never folded the segment chain"
    want = _all_values(node)
    node.close()
    re = Node(dc_id="dc1", config=cfg)
    assert _all_values(re) == want
    re.close()


def test_delta_rides_the_document_to_the_adopt_and_no_further(
        tmp_path, monkeypatch):
    """The segmented persist reads ``doc["delta"]`` and leaves it for
    the adopt, which installs exactly those seeds (ISSUE 32) and drops
    the key: neither the manifest on disk nor the adopted document
    carries it."""
    cfg = _mk(tmp_path)
    node = Node(dc_id="dc1", config=cfg)
    for i in range(10):
        _commit(node, i, [(f"ctr_{i}", "counter_pn", 1)])
    pm = node.partitions[0]
    assert pm.checkpoint_now() is not None
    _commit(node, 1000, [("ctr_3", "counter_pn", 5)])
    seen = {}
    real = pm.log.adopt_checkpoint

    def adopt(doc, trunc=None):
        seen["delta"] = dict(doc["delta"])
        return real(doc, trunc)

    monkeypatch.setattr(pm.log, "adopt_checkpoint", adopt)
    doc = pm.checkpoint_now()
    assert set(seen["delta"]) == {"ctr_3"}
    assert "delta" not in doc and len(doc["keys"]) == 10
    manifest = CheckpointStore._parse(
        open(pm.log.path + ".ckpt", "rb").read())
    assert "delta" not in manifest and "keys" not in manifest
    assert pm.log.seed_for("ctr_3")[1] == 6
    node.close()


def test_compacting_cut_still_adopts_only_its_delta(tmp_path):
    """A cut that elects compaction writes every live seed into one
    segment, yet what changed since the adopted document is still its
    delta: the live seeds of the untouched keys stay the same objects,
    and a reopen from the compacted segment reads them all."""
    cfg = _mk(tmp_path, ckpt_seg_waste_frac=0.2)
    node = Node(dc_id="dc1", config=cfg)
    keys = [f"ctr_{i}" for i in range(6)]
    for i, k in enumerate(keys):
        _commit(node, i, [(k, "counter_pn", 1)])
    pm = node.partitions[0]
    assert pm.checkpoint_now() is not None
    from antidote_tpu import stats

    compactions = stats.registry.ckpt_seg_compactions.value()
    n = 100
    while stats.registry.ckpt_seg_compactions.value() == compactions:
        before = dict(pm.log.ckpt_seeds)
        for k in keys[:3]:
            _commit(node, n, [(k, "counter_pn", 1)])
            n += 1
        assert pm.checkpoint_now() is not None
        assert n < 200, "compaction never elected"
    assert len(_segfiles(node)) == 1
    seeds = pm.log.ckpt_seeds
    assert [k for k in keys if seeds[k] is not before[k]] == keys[:3]
    want = {k: seeds[k][:2] for k in keys}
    node.close()
    re = Node(dc_id="dc1", config=cfg)
    got = re.partitions[0].log.ckpt_seeds
    assert {k: got[k][:2] for k in keys} == want
    re.close()


# ------------------------------------------------------- torn / loud


def _one_ckpt_node(tmp_path, n_txns=30):
    cfg = _mk(tmp_path)
    node = Node(dc_id="dc1", config=cfg)
    _workload(node, n_txns=n_txns)
    pm = node.partitions[0]
    assert pm.checkpoint_now() is not None
    want = _all_values(node)
    node.close()
    return cfg, want


def test_torn_manifest_at_every_byte_loads_none(tmp_path):
    cfg, _want = _one_ckpt_node(tmp_path)
    path = glob.glob(os.path.join(cfg.data_dir, "*.ckpt"))[0]
    raw = open(path, "rb").read()
    st = CheckpointStore(path, ckpt_from_config(Config()))
    for cut in range(len(raw)):
        open(path, "wb").write(raw[:cut])
        assert st.load_doc() is None, \
            f"torn manifest prefix of {cut} bytes loaded"
    open(path, "wb").write(raw)
    assert st.load_doc() is not None


def test_torn_segment_at_every_byte_refuses_whole_checkpoint(
        tmp_path, caplog):
    """ANY torn byte of ANY segment refuses the whole document —
    loudly — and recovery falls back to the (exact) full scan."""
    import logging

    cfg, want = _one_ckpt_node(tmp_path)
    seg = _segfiles_dir(cfg.data_dir)[0]
    path = glob.glob(os.path.join(cfg.data_dir, "*.ckpt"))[0]
    raw = open(seg, "rb").read()
    st = CheckpointStore(path, ckpt_from_config(Config()))
    for cut in range(0, len(raw), max(1, len(raw) // 64)):
        open(seg, "wb").write(raw[:cut])
        with caplog.at_level(logging.ERROR):
            caplog.clear()
            assert st.load_doc() is None, \
                f"torn segment prefix of {cut} bytes loaded"
        assert any("missing or torn" in r.message
                   for r in caplog.records), \
            "segment refusal must be loud"
    open(seg, "wb").write(raw)
    assert st.load_doc() is not None
    # and a recovery over the torn state still serves exact values
    open(seg, "wb").write(raw[: len(raw) // 2])
    node = Node(dc_id="dc1", config=cfg)
    assert node.partitions[0].log.suffix_start == 0  # full scan
    assert _all_values(node) == want
    node.close()


def test_missing_segment_refuses_loudly(tmp_path, caplog):
    import logging

    cfg, _want = _one_ckpt_node(tmp_path)
    seg = _segfiles_dir(cfg.data_dir)[0]
    os.remove(seg)
    path = glob.glob(os.path.join(cfg.data_dir, "*.ckpt"))[0]
    st = CheckpointStore(path, ckpt_from_config(Config()))
    with caplog.at_level(logging.ERROR):
        assert st.load_doc() is None
    assert any("missing or torn" in r.message
               for r in caplog.records)


# ------------------------------------------------ compaction safety


def test_crash_mid_compaction_keeps_old_manifest_authoritative(
        tmp_path, monkeypatch):
    """A compaction that dies before the manifest rename leaves the
    previous manifest + its segments fully live; the next checkpoint
    retries and succeeds."""
    cfg = _mk(tmp_path, ckpt_seg_waste_frac=0.01)
    node = Node(dc_id="dc1", config=cfg)
    for i in range(6):
        _commit(node, i, [(f"ctr_{i}", "counter_pn", 1)])
    pm = node.partitions[0]
    assert pm.checkpoint_now() is not None
    prev_doc_raw = open(pm.log.ckpt.path, "rb").read()
    prev_keys = dict(pm.log.ckpt_seeds)

    # next cut re-folds a key AND trips the waste fraction -> it will
    # try to compact; fail its manifest rename (the commit point)
    _commit(node, 100, [("ctr_0", "counter_pn", 7)])
    import antidote_tpu.oplog.checkpoint as ckpt_mod

    real_replace = os.replace

    def boom(src, dst, *a, **kw):
        if dst.endswith(".ckpt"):
            raise OSError("injected crash at the manifest rename")
        return real_replace(src, dst, *a, **kw)

    monkeypatch.setattr(ckpt_mod.os, "replace", boom)
    with pytest.raises(Exception):
        pm.checkpoint_now()
    monkeypatch.setattr(ckpt_mod.os, "replace", real_replace)
    # old manifest bytes untouched and still loadable with ALL seeds
    assert open(pm.log.ckpt.path, "rb").read() == prev_doc_raw
    loaded = pm.log.ckpt.load_doc()
    assert loaded is not None and set(loaded["keys"]) == \
        set(prev_keys)
    # the retry (dirty set was merged back) lands the compaction
    assert pm.checkpoint_now() is not None
    want = _all_values(node)
    node.close()
    re = Node(dc_id="dc1", config=cfg)
    assert _all_values(re) == want
    assert re.partitions[0].value_snapshot("ctr_0", "counter_pn") \
        == 1 + 7
    re.close()


def test_compaction_vs_concurrent_checkpoint_single_flight(tmp_path):
    """Racing checkpoint_now calls share the inflight guard: no
    stacked writers, no torn segment chains — the surviving manifest
    loads with the full seed set whichever thread led."""
    cfg = _mk(tmp_path, ckpt_seg_waste_frac=0.01)
    node = Node(dc_id="dc1", config=cfg)
    for i in range(12):
        _commit(node, i, [(f"ctr_{i}", "counter_pn", 1)])
    pm = node.partitions[0]
    assert pm.checkpoint_now() is not None
    errs = []
    n_base = 1000

    def churn_and_cut(tid):
        try:
            for r in range(4):
                _commit(node, n_base + tid * 100 + r,
                        [(f"ctr_{(tid + r) % 12}", "counter_pn", 1)])
                pm.checkpoint_now()
        except Exception as e:  # noqa: BLE001 — asserted below
            errs.append(e)

    ts = [threading.Thread(target=churn_and_cut, args=(t,))
          for t in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(30)
    assert not errs, errs
    assert pm.checkpoint_now() is not None  # quiesced final cut
    doc = pm.log.ckpt.load_doc()
    assert doc is not None and len(doc["keys"]) == 12
    want = _all_values(node)
    node.close()
    re = Node(dc_id="dc1", config=cfg)
    assert _all_values(re) == want
    re.close()


def test_monolithic_to_segmented_flip_carries_all_seeds(tmp_path):
    """The first segmented cut after a knob flip must persist the
    FULL carried seed set (a monolithic document's seeds live in no
    segment) — pre-guard, they silently vanished from the merge."""
    cfg = _mk(tmp_path, ckpt_segmented=False)
    node = Node(dc_id="dc1", config=cfg)
    for i in range(10):
        _commit(node, i, [(f"ctr_{i}", "counter_pn", 1)])
    assert node.partitions[0].checkpoint_now() is not None
    node.close()
    seg_cfg = _mk(tmp_path, ckpt_segmented=True)
    node = Node(dc_id="dc1", config=seg_cfg)
    pm = node.partitions[0]
    _commit(node, 100, [("ctr_0", "counter_pn", 1)])
    assert pm.checkpoint_now() is not None
    doc = pm.log.ckpt.load_doc()
    assert doc is not None and len(doc["keys"]) == 10, \
        "monolithic-carried seeds vanished across the knob flip"
    node.close()


# -------------------------------------------- device seed round trip


SEED_CASES = [
    ("counter_pn", [5, -2, 9]),
    ("set_aw", [("add", [("a", ("dc1", 1), ())]),
                ("add", [("b", ("dc1", 2), ())]),
                ("rmv", [("a", (("dc1", 1),))])]),
    ("register_mv", [("asgn", "x", ("dc1", 3), ()),
                     ("asgn", "y", ("dc2", 1), ())]),
    ("flag_ew", [("en", ("dc1", 4), ())]),
    ("set_go", [("p", "q"), ("r",)]),
    ("register_lww", [(100, ("dc1", 1), "old"),
                      (200, ("dc2", 2), "new")]),
]


@pytest.mark.parametrize("tn,effects", SEED_CASES,
                         ids=[c[0] for c in SEED_CASES])
def test_device_seed_round_trips_each_type(tn, effects):
    """seed_effects(read()) staged onto a FRESH plane reads back the
    identical state — the inverse pair the seeded-base init rests on
    — and the seeded plane replay-gates below the seed frontier."""
    from antidote_tpu.mat.device_plane import DevicePlane, ReadBelowBase
    from antidote_tpu.mat.materializer import Payload

    src = DevicePlane()
    key = f"k_{tn}"
    vc = VC({"dc1": 50, "dc2": 40})
    for i, eff in enumerate(effects):
        src.planes[tn].stage(key, Payload(
            key=key, type_name=tn, effect=eff, commit_dc="dc1",
            commit_time=10 + i, snapshot_vc=VC({"dc1": 10 + i}),
            txid=("t", i), certified=True))
    state = src.planes[tn].read(key, None)

    dst = DevicePlane()
    assert dst.seed_state(key, tn, state, vc) is True
    dst.planes[tn].gc(vc)  # what install_ckpt_seeds does per plane
    assert dst.owns(tn, key) and key not in dst.host_only
    assert dst.planes[tn].read(key, None) == state
    # reads covering the frontier serve; below it replay-gate to the
    # log path (the base VC is the seed frontier)
    assert dst.planes[tn].read(key, vc) == state
    with pytest.raises(ReadBelowBase):
        dst.planes[tn].read(key, VC({"dc1": 1}))


def test_device_seed_refuses_lossy_and_unrepresentable():
    from antidote_tpu.mat.device_plane import DevicePlane

    dp = DevicePlane()
    assert dp.seed_state("k", "set_rw", {}, VC({"dc1": 1})) is False
    assert dp.seed_state("k", "rga", [], VC({"dc1": 1})) is False
    assert dp.seed_state("k", "map_go", {}, VC({"dc1": 1})) is False
    # an empty frontier cannot stamp a commit VC — host path
    assert dp.seed_state("k", "counter_pn", 3, VC()) is False
    # host-pinned keys stay host-pinned
    dp.host_only.add("pinned")
    assert dp.seed_state("pinned", "counter_pn", 3,
                         VC({"dc1": 1})) is False


def test_dot_heavy_seed_chunk_folds_past_the_lane_budget():
    """A seed with far more rows than the per-key ring lanes must
    chunk-fold instead of overflow-evicting at boot (there is no
    stable horizon for the overflow retry)."""
    from antidote_tpu.mat.device_plane import DevicePlane

    dp = DevicePlane()
    lanes = dp.planes["set_aw"].n_lanes
    state = {f"e{i}": frozenset({("dc1", i + 1)})
             for i in range(3 * lanes + 2)}
    vc = VC({"dc1": 1000})
    assert dp.seed_state("fat", "set_aw", state, vc) is True
    dp.planes["set_aw"].gc(vc)
    assert dp.owns("set_aw", "fat") and "fat" not in dp.host_only
    assert dp.planes["set_aw"].read("fat", None) == state
