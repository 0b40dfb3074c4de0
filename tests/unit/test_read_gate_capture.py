"""A read captures nowhere while it waits anywhere (PR 28): every
batched read goes through ``txn.manager.read_requests``, whose captures
(``PartitionManager.read_many_begin``) never wait and whose waits
(``read_gate``) hold no partition's ``_dev_readers`` count.

(a) the read/commit deadlock's own schedule, made deterministic with
two partitions and events; (c) waves that meet a not-ready partition
leak no count; (d) a capture checks again whatever a gate found;
(e) the timeout and its message; (g) the recorded spans."""

import random
import threading
import time

import pytest

from antidote_tpu import stats
from antidote_tpu.clocks import VC
from antidote_tpu.mat.device_plane import DevicePlane
from antidote_tpu.mat.ingest import IngestSettings
from antidote_tpu.obs.spans import tracer
from antidote_tpu.oplog.partition import PartitionLog
from antidote_tpu.txn.clock import HybridClock
from antidote_tpu.txn import manager
from antidote_tpu.txn.manager import PartitionManager, read_many_fused

CK = "counter_pn"
T = ("dc1", "T")


def make_pms(tmp_path, n=2, flush_ops=1, timeout=0.5):
    """``n`` bare partitions on one clock.  ``flush_ops=1``: a publish
    reaches the device at once and leaves nothing pending; a larger
    number leaves it pending until a read's gate flushes (no staging
    window: threshold-only flushing)."""
    clock = HybridClock()
    pms = []
    for p in range(n):
        log = PartitionLog(str(tmp_path / f"p{p}.log"), partition=p)
        pms.append(PartitionManager(
            p, "dc1", log, clock, read_wait_timeout=timeout,
            device_plane=DevicePlane(
                flush_ops=flush_ops, gc_ops=10**6,
                ingest_settings=IngestSettings(coalesce_us=0))))
    return pms


@pytest.fixture
def ab(tmp_path):
    pms = make_pms(tmp_path)
    yield pms
    for pm in pms:
        pm.log.close()


def now_vc(pm):
    return VC({"dc1": pm.clock.now_us()})


_serial = iter(range(1, 10**9))


def write(pm, key, delta):
    """One committed counter increment; returns its commit time."""
    txid = ("dc1", f"w{next(_serial)}")
    pm.stage_update(txid, key, CK, delta)
    return pm.single_commit(txid, now_vc(pm))


def prepare_t(pairs, txid=T):
    """Prepare ``txid`` on every (pm, key, delta) of ``pairs``; returns
    its snapshot and the commit time a coordinator would choose."""
    snap = now_vc(pairs[0][0])
    for pm, key, delta in pairs:
        pm.stage_update(txid, key, CK, delta)
    return snap, max(pm.prepare(txid, snap) for pm, _k, _d in pairs)


class Entered:
    """Wraps a bound method: says when a thread has entered it, and
    what ``watch()`` read at that moment."""

    def __init__(self, obj, name, watch=lambda: None):
        self.event, self.seen = threading.Event(), []
        orig = getattr(obj, name)

        def hook(*a, **kw):
            self.seen.append(watch())
            self.event.set()
            return orig(*a, **kw)

        setattr(obj, name, hook)


def in_thread(fn):
    box = {}

    def run():
        try:
            box["value"] = fn()
        except BaseException as e:  # noqa: BLE001 — handed to the test
            box["error"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t, box


def assert_publish_does_not_block(pms):
    """A device mutation waits for the reader count to be zero: one a
    partition, each on a thread of its own, all done in seconds."""
    threads = [in_thread(lambda pm=pm: write(pm, "after", 1))[0]
               for pm in pms]
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive(), "a publish waits for a leaked reader"


# ----------------------------------------------- (a) the deadlock's schedule


def one_key_at_a_time(groups, vc):
    """``read_many_fused``'s answer through PartitionManager.read."""
    return {(k, t): pm.read(k, t, vc)
            for pm, items in groups for k, t in items}


#: the entries that take a snapshot over partitions: both are
#: read_requests, the second with one request of one item at a time
ENTRIES = {"fused": read_many_fused, "read": one_key_at_a_time}


def blocked_reader(ab, entry=read_many_fused):
    """T prepared on A (key a2) and on B (key b1); a reader of a1 on A
    and b1 on B at a snapshot above T's prepare times, started, and
    stopped where it waits for T on B.  Returns what the test needs to
    let it go."""
    A, B = ab
    write(A, "a1", 1)
    write(B, "b1", 2)
    for pm in ab:
        pm._val_cache.clear()   # both keys fold on the device
    snap_t, ct = prepare_t([(A, "a2", 10), (B, "b1", 20)])
    s = now_vc(A)
    at_b = Entered(B, "_await_unprepared", lambda: A._dev_readers)
    reader, box = in_thread(lambda: entry(
        [(A, [("a1", CK)]), (B, [("b1", CK)])], s))
    assert at_b.event.wait(5), "the reader never reached B's wait"
    return A, B, snap_t, ct, s, at_b, reader, box


@pytest.mark.parametrize("entry", sorted(ENTRIES))
def test_a_reader_waiting_on_b_holds_no_count_on_a(ab, entry):
    """PR 27's read_many_fused began on A, kept A's count and stood in
    B's prepared wait, while T's commit on A stood in
    _wait_device_quiesce: 0.5 s here, 5 s served, then the read
    failed."""
    A, B, snap_t, ct, _s, at_b, reader, box = blocked_reader(
        ab, ENTRIES[entry])
    assert at_b.seen == [0], "the reader waits on B holding A's count"
    t0 = time.monotonic()
    commit_a, cbox = in_thread(lambda: A.commit(T, ct, snap_t))
    commit_a.join(timeout=5)
    assert not commit_a.is_alive() and "error" not in cbox
    assert time.monotonic() - t0 < 0.4, "T's commit on A waited"
    B.commit(T, ct, snap_t)
    reader.join(timeout=5)
    assert not reader.is_alive()
    assert box.get("error") is None, box
    # T committed at or below the reader's snapshot: it is seen
    assert box["value"] == {("a1", CK): 1, ("b1", CK): 22}
    assert [pm._dev_readers for pm in ab] == [0, 0]
    assert A.read_many([("a2", CK)], now_vc(A)) == {("a2", CK): 10}
    assert_publish_does_not_block(ab)


def test_a_single_partition_read_is_the_driver_with_one_request(ab):
    A, _B = ab
    write(A, "a1", 1)
    snap_t, ct = prepare_t([(A, "a1", 5)])
    s = now_vc(A)
    at_a = Entered(A, "_await_unprepared", lambda: A._dev_readers)
    reader, box = in_thread(lambda: A.read_many([("a1", CK)], s))
    assert at_a.event.wait(5)
    A.commit(T, ct, snap_t)
    reader.join(timeout=5)
    assert box.get("value") == {("a1", CK): 6}, box
    assert at_a.seen == [0] and A._dev_readers == 0


def test_requests_answer_in_order_each_with_values_or_its_error(ab):
    """One partition refusing (ownership in doubt) fails its own
    request and no other."""
    A, B = ab
    write(A, "a1", 1)
    write(B, "b1", 2)
    B.parked = True
    got = manager.read_requests([(B, [("b1", CK)], None, None),
                                 (A, [("a1", CK)], None, None)])
    assert type(got[0]).__name__ == "PartitionRetired"
    assert got[1] == {("a1", CK): 1}
    with pytest.raises(Exception, match="ownership in doubt"):
        read_many_fused([(A, [("a1", CK)]), (B, [("b1", CK)])], None)
    assert [pm._dev_readers for pm in ab] == [0, 0]


# ------------------------------------------- (g) what the spans show of it


def test_no_prepared_wait_has_a_captured_sibling_in_the_spans(ab):
    """In the recorded spans of the reader's thread every capture made
    before a ``pm_prepared_wait`` began (``device_prepare``) was
    finished before it began (``device_fetch``)."""
    old_rate = tracer.sample_rate
    tracer.clear()
    tracer.sample_rate = 1.0
    try:
        A, B, snap_t, ct, _s, _at_b, reader, box = blocked_reader(ab)
        A.commit(T, ct, snap_t)
        B.commit(T, ct, snap_t)
        reader.join(timeout=5)
        assert box.get("error") is None, box
        spans = [sp for sp in tracer.spans() if sp.tid == reader.ident]
    finally:
        tracer.sample_rate = old_rate
        tracer.clear()
    waits = [sp for sp in spans if sp.name == "pm_prepared_wait"]
    assert len(waits) == 1 and waits[0].kind == "wait"
    assert waits[0].args["partition"] == B.partition
    w0 = waits[0].start_us
    captured = [sp for sp in spans
                if sp.name == "device_prepare" and sp.start_us <= w0]
    finished = [sp for sp in spans if sp.name == "device_fetch"
                and sp.start_us + sp.dur_us <= w0]
    assert len(captured) == 1 == len(finished), (captured, finished)
    # and B's own capture comes after the wait is over
    later = [sp for sp in spans if sp.name == "device_prepare"
             and sp.start_us >= w0 + waits[0].dur_us]
    assert len(later) == 1
    folds = [sp for sp in spans if sp.name == "read_serve_fold"]
    assert len(folds) == 3      # A, B not ready, B again


# ------------------------------------------------- (c) nothing leaks, ever


@pytest.mark.parametrize("seed", [1, 2])
def test_200_seeded_rounds_with_a_partition_not_ready_leak_nothing(
        tmp_path, seed):
    """Each round a seeded choice of partitions is not ready — pending
    operations on a key of the read, or a transaction prepared on it
    that commits once the reader waits — and a read over all of them
    must answer the model's values and hold nothing afterwards."""
    rng = random.Random(seed)
    pms = make_pms(tmp_path, n=3, flush_ops=10**6, timeout=5.0)
    model = {}
    for pm in pms:
        for i in range(4):
            key = f"k{pm.partition}_{i}"
            write(pm, key, i + 1)
            model[key] = i + 1
    not_ready = []
    for pm in pms:
        def counted(*a, _begin=pm.read_many_begin, **kw):
            cap = _begin(*a, **kw)
            not_ready.append(cap is None)
            return cap

        pm.read_many_begin = counted
    retried = 0
    for rnd in range(200):
        groups = [(pm, [(f"k{pm.partition}_{i}", CK)
                        for i in rng.sample(range(4), 2)])
                  for pm in rng.sample(pms, rng.randint(1, 3))]
        prepared = []
        for pm, items in groups:
            how = rng.choice(["ready", "ready", "pending", "prepared"])
            key = items[0][0]
            if how == "pending":
                write(pm, key, 3)
                model[key] += 3
            elif how == "prepared" and not prepared:
                prepared.append((pm, key, 7))
            if rng.random() < 0.5:
                pm._val_cache.clear()
        if prepared:
            pm_t = prepared[0][0]
            txid = ("dc1", f"T{rnd}")
            snap_t, ct = prepare_t(prepared, txid)
            gate = Entered(pm_t, "_await_unprepared",
                           lambda: [pm._dev_readers for pm in pms])
            reader, box = in_thread(
                lambda: read_many_fused(groups, now_vc(pm_t)))
            assert gate.event.wait(5), rnd
            assert gate.seen[0] == [0, 0, 0], (rnd, gate.seen)
            pm_t.commit(txid, ct, snap_t)
            del pm_t._await_unprepared      # the hook
            model[prepared[0][1]] += 7
            reader.join(timeout=10)
            assert not reader.is_alive() and "error" not in box, rnd
            got = box["value"]
            retried += 1
        else:
            got = read_many_fused(groups, now_vc(pms[0]))
        want = {(k, CK): model[k] for _pm, items in groups
                for k, _t in items}
        assert got == want, rnd
        assert [pm._dev_readers for pm in pms] == [0, 0, 0], rnd
    assert retried > 10 and sum(not_ready) > 50, sum(not_ready)
    assert_publish_does_not_block(pms)
    for pm in pms:
        pm.log.close()


# ------------------------------- (d) a capture trusts no gate's answer


class _ClockAt:
    """Stands in for the partition's clock while one prepare draws."""

    def __init__(self, t):
        self.t = t

    def now_us(self):
        return self.t


def counters():
    reg = stats.registry
    return (reg.read_cache_hits.value(), reg.read_cache_misses.value(),
            reg.read_dispatches.value())


def test_capture_refuses_what_prepared_after_the_gate_passed(ab):
    """The gate found nothing prepared; a transaction then enters the
    table with a prepare time at or below the snapshot (a clock cannot
    issue one once the gate's clock wait is over: the entry is made by
    hand, to show that the capture makes its own check): the capture
    says not ready, having taken and counted nothing."""
    A, _B = ab
    write(A, "a1", 1)
    A._val_cache.clear()
    s = now_vc(A)
    items = [("a1", CK)]
    A.read_gate(items, s, None, time.monotonic() + 1)
    A.stage_update(T, "a1", CK, 4)
    clock, A.clock = A.clock, _ClockAt(s.get_dc("dc1"))
    try:
        pt = A.prepare(T, s)
    finally:
        A.clock = clock
    before = counters()
    assert A.read_many_begin(items, s) is None
    assert A._dev_readers == 0 and counters() == before
    # T's own reads pass its own prepare
    out, batches = A.read_many_begin(items, s, T)
    assert A.read_many_finish(out, batches, s, T) == {("a1", CK): 1}
    A.commit(T, pt, s)
    A._val_cache.clear()
    out, batches = A.read_many_begin(items, s)
    assert A._dev_readers == 1
    assert A.read_many_finish(out, batches, s) == {("a1", CK): 5}
    assert A._dev_readers == 0


def test_capture_refuses_before_the_clock_has_passed_the_snapshot(ab):
    A, _B = ab
    write(A, "a1", 1)
    ahead = VC({"dc1": A.clock.now_us() + 200_000})
    assert A.read_many_begin([("a1", CK)], ahead) is None
    t0 = time.monotonic()
    assert A.read_many([("a1", CK)], ahead) == {("a1", CK): 1}
    assert 0.15 < time.monotonic() - t0 < 2.0    # the gate's clock wait


def test_capture_refuses_a_plane_that_gained_pending_keys_after_the_gate(
        tmp_path):
    (A,) = make_pms(tmp_path, n=1, flush_ops=10**6)
    plane = A.device.planes[CK]
    write(A, "a1", 1)
    A._val_cache.clear()
    items = [("a1", CK)]
    assert "a1" in plane.pending_keys
    assert A.read_many_begin(items, None) is None
    A.read_gate(items, None, None, time.monotonic() + 1)
    assert not plane.pending_keys           # the gate flushed
    write(A, "a1", 2)                       # ... and a commit came after
    A._val_cache.clear()
    before = counters()
    assert A.read_many_begin(items, None) is None
    assert A._dev_readers == 0 and counters() == before
    assert "a1" in plane.pending_keys       # a capture never flushes
    # a cached value needs no fold, so pending operations do not stop it
    write(A, "a2", 9)
    assert "a2" in plane.pending_keys
    out, batches = A.read_many_begin([("a2", CK)], None)
    assert (out, batches) == ({("a2", CK): 9}, [])
    assert A.read_many(items, None) == {("a1", CK): 3}
    assert A._dev_readers == 0
    A.log.close()


def test_the_gate_waits_for_readers_before_it_flushes(tmp_path):
    """The flush donates the buffers a reader of an older capture still
    holds: the gate stands in _wait_device_quiesce until that reader
    has finished."""
    (A,) = make_pms(tmp_path, n=1, flush_ops=10**6)
    write(A, "a1", 1)
    A.read_gate([("a1", CK)], None, None, time.monotonic() + 1)
    write(A, "a2", 2)
    A._val_cache.clear()
    out, batches = A.read_many_begin([("a1", CK)], None)   # holds a count
    assert A._dev_readers == 1
    quiesce = Entered(A, "_wait_device_quiesce", lambda: A._dev_readers)
    gate, box = in_thread(lambda: A.read_gate(
        [("a2", CK)], None, None, time.monotonic() + 5))
    assert quiesce.event.wait(5) and quiesce.seen == [1]
    gate.join(timeout=0.2)
    assert gate.is_alive(), "the gate flushed under a reader"
    assert A.read_many_finish(out, batches, None) == {("a1", CK): 1}
    gate.join(timeout=5)
    assert not gate.is_alive() and "error" not in box
    assert not A.device.planes[CK].pending_keys
    A.log.close()


# ------------------------------------------------------- (e) the timeout


@pytest.mark.parametrize("shape", ["two_partitions", "one_partition",
                                   "one_key"])
def test_the_timeout_fires_with_its_message_and_holds_nothing(ab, shape):
    A, B = ab
    write(A, "a1", 1)
    write(B, "b1", 2)
    for pm in ab:
        pm._val_cache.clear()
    snap_t, ct = prepare_t([(B, "b1", 20)])
    s = now_vc(A)
    t0 = time.monotonic()
    with pytest.raises(TimeoutError) as err:
        if shape == "two_partitions":
            read_many_fused([(A, [("a1", CK)]), (B, [("b1", CK)])], s)
        elif shape == "one_partition":
            B.read_many([("b1", CK), ("b0", CK)], s)
        else:
            B.read("b1", CK, s)
    assert str(err.value) == "batched read blocked on prepared txn"
    assert 0.45 < time.monotonic() - t0 < 3.0
    assert [pm._dev_readers for pm in ab] == [0, 0]
    # the transaction resolves later: nothing was left behind
    B.commit(T, ct, snap_t)
    assert read_many_fused([(A, [("a1", CK)]), (B, [("b1", CK)])],
                           now_vc(A)) == {("a1", CK): 1, ("b1", CK): 22}
    assert_publish_does_not_block(ab)


@pytest.mark.parametrize("entry", ["read_many", "read"])
def test_the_timeout_runs_from_the_first_gate_across_waves(ab, entry):
    """A request that is gated, captured "not ready" and gated again
    does not start its 0.5 s anew."""
    A, _B = ab
    write(A, "a1", 1)
    A._val_cache.clear()
    calls = []
    orig = A.read_gate

    def gate_that_finds_nothing(items, vc, txid, deadline):
        calls.append(deadline)
        time.sleep(0.1)
        if time.monotonic() >= deadline:
            return orig(items, vc, txid, deadline)

    A.read_gate = gate_that_finds_nothing
    A.read_many_begin = lambda *a, **kw: None       # never ready
    t0 = time.monotonic()
    with pytest.raises(TimeoutError,
                       match="batched read blocked on prepared txn"):
        if entry == "read_many":
            A.read_many([("a1", CK)], None)
        else:
            A.read("a1", CK, None)
    assert 0.45 < time.monotonic() - t0 < 3.0
    assert len(calls) >= 4 and len(set(calls)) == 1
