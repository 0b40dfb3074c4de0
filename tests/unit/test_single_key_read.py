"""A key is read one way (PR 30): one key is the batched read with one
key in it, in the planes (``plane.read`` is ``read_many`` at the first
dispatch bucket; no B=1 program is compiled or warmed) and in the
partition manager (``PartitionManager.read`` is ``read_requests`` with
one request of one item: the gate's waits, the capture's cache, log
and device branches, the finish's counts serve it)."""

import threading
import time

import pytest

from antidote_tpu import stats
from antidote_tpu.clocks import VC
from antidote_tpu.crdt import DownstreamCtx, get_type
from antidote_tpu.mat import device_plane, ingest, store
from antidote_tpu.mat.device_plane import DevicePlane
from antidote_tpu.mat.ingest import IngestSettings
from antidote_tpu.mat.materializer import Payload
from antidote_tpu.oplog.partition import PartitionLog
from antidote_tpu.txn.clock import HybridClock
from antidote_tpu.txn.manager import PartitionManager

#: operations whose host state the device fold reconstructs exactly
#: (the lossy types: no two live dots of one DC on one element)
OPS = {
    "counter_pn": [("increment", 5), ("decrement", 2)],
    "set_aw": [("add", "a"), ("add", "b"), ("remove", "a")],
    "register_mv": [("assign", "x"), ("assign", "y")],
    "flag_ew": [("enable", ())],
    "set_rw": [("add", "a"), ("add", "b"), ("remove", "a")],
    "flag_dw": [("enable", ()), ("disable", ())],
    "set_go": [("add", "p"), ("add_all", ["q", "r"])],
    "register_lww": [("assign", "old"), ("assign", "new")],
    "map_rr": [("update", (("tags", "set_aw"), ("add", "a"))),
               ("update", (("on", "flag_dw"), ("enable", ()))),
               ("update", (("tags", "set_aw"), ("add", "b")))],
}


def stage_ops(plane, key, type_name, ops):
    """Stage ``ops`` on ``plane`` through the host CRDT's own
    downstream; returns the host CRDT's state after them."""
    cls = get_type(type_name)
    state = cls.new()
    for i, op in enumerate(ops):
        ct = 10 + i
        eff = cls.downstream(op, state, DownstreamCtx("dc1", seq=ct - 1))
        state = cls.update(eff, state)
        plane.stage(key, Payload(
            key=key, type_name=type_name, effect=eff, commit_dc="dc1",
            commit_time=ct, snapshot_vc=VC({"dc1": ct - 1}),
            txid=("t", i), certified=True))
    return state


@pytest.fixture
def index_shapes(monkeypatch):
    """The shape of every index array handed to a store read."""
    seen = []
    for name in [n for n in dir(store) if n.endswith("_read_keys")]:
        fn = getattr(store, name)

        def spy(st, key_idx, rv, _fn=fn, _name=name):
            seen.append((_name, tuple(key_idx.shape)))
            return _fn(st, key_idx, rv)

        monkeypatch.setattr(store, name, spy)
    return seen


@pytest.mark.parametrize("type_name", sorted(OPS))
def test_one_key_is_the_batched_read_with_one_key_in_it(
        type_name, index_shapes):
    plane = DevicePlane().planes[type_name]
    want = stage_ops(plane, "k", type_name, OPS[type_name])
    stage_ops(plane, "other", type_name, OPS[type_name][:1])
    at = VC({"dc1": 100})
    for vc in (None, at):
        assert plane.read("k", vc) == want
        assert plane.read_many(["k"], vc)["k"] == want
    # ... and below the first operation the key is still bottom
    assert plane.read("k", VC({"dc1": 5})) == get_type(type_name).new()
    assert index_shapes, "no store read ran"
    b = ingest.bucket(1)
    assert {shape for _n, shape in index_shapes} == {(b,)}, index_shapes
    with pytest.raises(device_plane.ReadBelowBase):
        plane.read("never staged", None)


def test_after_a_growth_no_one_key_read_program_is_warmed():
    plane = DevicePlane(key_capacity=4).planes["counter_pn"]
    for i in range(6):                      # outgrows 4 keys
        stage_ops(plane, f"k{i}", "counter_pn", [("increment", 1)])
    assert plane.capacity == 8
    for t in threading.enumerate():
        if t.name.startswith("warm"):
            t.join(timeout=60)
    reads = [k for k in device_plane._WARMED if k[0] == "read"]
    grown = [k for k in reads if k[1] == id(type(plane))
             and (8,) in [shape for shape, _dt in k[2]]]
    assert grown, reads
    assert {k[-1] for k in reads} == {ingest.bucket(1)}, reads


# --------------------------------------------- the partition manager's


def make_pm(tmp_path, **plane_kw):
    log = PartitionLog(str(tmp_path / "p0.log"), partition=0)
    return PartitionManager(
        0, "dc1", log, HybridClock(), read_wait_timeout=0.5,
        device_plane=DevicePlane(
            gc_ops=10**6, ingest_settings=IngestSettings(coalesce_us=0),
            **plane_kw))


_serial = iter(range(1, 10**9))


def commit(pm, key, type_name, op):
    """One committed operation, its downstream generated from the
    log's exact state; returns that state after it."""
    cls = get_type(type_name)
    txid = ("dc1", f"w{next(_serial)}")
    with pm._lock:
        state = pm._read_from_log(key, type_name, None)
    eff = cls.downstream(op, state, DownstreamCtx(
        "dc1", mint=lambda: ("dc1", pm.clock.now_us())))
    pm.stage_update(txid, key, type_name, eff)
    pm.single_commit(txid, VC({"dc1": pm.clock.now_us()}))
    return cls.update(eff, state)


def counters():
    reg = stats.registry
    return (reg.read_cache_hits.value(), reg.read_cache_misses.value(),
            reg.read_dispatches.value())


def delta(before):
    return tuple(a - b for a, b in zip(counters(), before))


def test_exact_state_is_the_logs_and_no_lossy_entry_answers_it(tmp_path):
    """Two adds of one element by one DC: the host state holds both
    dots, the device fold the newer one."""
    pm = make_pm(tmp_path, flush_ops=1)
    commit(pm, "s", "set_rw", ("add", "a"))
    exact = commit(pm, "s", "set_rw", ("add", "a"))
    assert len(exact["a"][0]) == 2
    assert pm.device.owns("set_rw", "s")
    assert not pm.device.state_exact("set_rw", "s")
    pm._val_cache.clear()
    before = counters()
    assert pm.read("s", "set_rw", None, exact_state=True) == exact
    assert delta(before) == (0, 1, 0)       # a miss, and no device fold
    assert pm._val_cache["s"][3] is True
    # the log's answer is exact: it serves both kinds of read
    before = counters()
    assert pm.read("s", "set_rw", None) == exact
    assert pm.read("s", "set_rw", None, exact_state=True) == exact
    assert delta(before) == (2, 0, 0)
    # a plain read may cache the fold, which is the same VALUE ...
    pm._val_cache.clear()
    fold = pm.read("s", "set_rw", None)
    assert len(fold["a"][0]) == 1 and fold != exact
    assert get_type("set_rw").value(fold) == ["a"]
    assert pm._val_cache["s"][1] == fold and pm._val_cache["s"][3] is False
    # ... and never answers a reader that needs the exact state
    before = counters()
    assert pm.read("s", "set_rw", None, exact_state=True) == exact
    assert delta(before) == (0, 1, 0)
    assert pm.read_with_writeset("s", "set_rw", None, ("dc1", "t"), [],
                                 exact_state=True) == exact
    assert pm._dev_readers == 0
    pm.log.close()


def test_a_key_the_gates_flush_evicted_is_answered_from_the_log(tmp_path):
    """Five pending operations on a ring of two lanes and no stable
    time to fold at: the gate's flush evicts the key to the host path
    (a replay of the log), and the capture made again serves it
    there."""
    pm = make_pm(tmp_path, flush_ops=10**6, n_lanes=2)
    for i in range(5):
        commit(pm, "c", "counter_pn", ("increment", i + 1))
    plane = pm.device.planes["counter_pn"]
    assert "c" in plane.pending_keys and pm.device.owns("counter_pn", "c")
    pm._val_cache.clear()
    dispatched = stats.registry.read_dispatches.value()
    assert pm.read("c", "counter_pn", None) == 15
    assert not pm.device.owns("counter_pn", "c")
    assert "c" in pm.device.host_only
    assert stats.registry.read_dispatches.value() == dispatched
    assert pm.read("c", "counter_pn", VC({"dc1": pm.clock.now_us()})) == 15
    assert pm._dev_readers == 0
    pm.log.close()


def test_read_counts_a_key_once_as_the_batched_read_does(tmp_path):
    pm = make_pm(tmp_path, flush_ops=1)
    for key in ("k1", "k2"):
        commit(pm, key, "counter_pn", ("increment", 7))
    by_entry = {}
    for name, key, entry in [
            ("read", "k1", lambda: pm.read("k1", "counter_pn", None)),
            ("read_many", "k2", lambda: pm.read_many(
                [("k2", "counter_pn")], None)[("k2", "counter_pn")])]:
        pm._val_cache.clear()
        before = counters()
        assert entry() == 7
        first = delta(before)
        before = counters()
        assert entry() == 7
        by_entry[name] = (first, delta(before))
    # hits, misses, dispatches: a fold the first time, the cache then
    assert by_entry["read"] == ((0, 1, 1), (1, 0, 0))
    assert by_entry["read"] == by_entry["read_many"]
    pm.log.close()


def test_read_waits_in_the_gate_and_holds_no_lock_or_count(tmp_path):
    """The snapshot is ahead of the clock: the capture says not ready,
    the gate waits the clock out, and meanwhile a commit on the
    partition goes through."""
    pm = make_pm(tmp_path, flush_ops=1)
    commit(pm, "k", "counter_pn", ("increment", 1))
    ahead = VC({"dc1": pm.clock.now_us() + 300_000})
    box = {}
    t = threading.Thread(
        target=lambda: box.update(v=pm.read("k", "counter_pn", ahead)))
    t0 = time.monotonic()
    t.start()
    commit(pm, "k", "counter_pn", ("increment", 2))
    assert time.monotonic() - t0 < 0.25, "a commit waited for the read"
    t.join(timeout=5)
    assert not t.is_alive() and box == {"v": 3}
    assert time.monotonic() - t0 > 0.25     # the gate's clock wait
    assert pm._dev_readers == 0
    pm.log.close()
