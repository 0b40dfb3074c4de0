"""The serve drain under the one read rule (PR 28): a drain's groups
are requests to ``txn.manager.read_requests``.  A group that is not
ready (pending operations, a snapshot ahead of the clock, a prepared
transaction) is gated once the groups captured beside it have given
their reader counts back, and captured again by the same code; a
covered waiter that fails its revalidation is served again after
that, never while the drain holds a count."""

import threading
import time

import pytest

from antidote_tpu.clocks import VC
from antidote_tpu.crdt import DownstreamCtx, get_type
from antidote_tpu.mat.device_plane import DevicePlane
from antidote_tpu.mat.ingest import IngestSettings
from antidote_tpu.mat.materializer import Payload
from antidote_tpu.mat.serve import ReadServer
from antidote_tpu.oplog.partition import PartitionLog
from antidote_tpu.txn.clock import HybridClock
from antidote_tpu.txn.manager import PartitionManager

CK = "counter_pn"
T = ("dc1", "T")


@pytest.fixture
def pm(tmp_path):
    """A bare partition behind a serve window; a publish stays pending
    until a read's gate flushes it."""
    log = PartitionLog(str(tmp_path / "p0.log"), partition=0)
    pm = PartitionManager(
        0, "dc1", log, HybridClock(), read_wait_timeout=0.5,
        device_plane=DevicePlane(
            flush_ops=10**6, gc_ops=10**6,
            ingest_settings=IngestSettings(coalesce_us=0)))
    pm.read_server = ReadServer(pm)
    yield pm
    log.close()


def now_vc(pm):
    return VC({"dc1": pm.clock.now_us()})


_serial = iter(range(1, 10**9))


def write(pm, key, delta):
    txid = ("dc1", f"w{next(_serial)}")
    pm.stage_update(txid, key, CK, delta)
    return pm.single_commit(txid, now_vc(pm))


def flushed(pm, *keys):
    pm.read_gate([(k, CK) for k in keys], None, None,
                 time.monotonic() + 1)
    pm._val_cache.clear()


def entered(obj, name, watch):
    """Wrap ``obj.name``: an event set when a thread enters it, and
    what ``watch()`` read each time."""
    event, seen, orig = threading.Event(), [], getattr(obj, name)

    def hook(*a, **kw):
        seen.append(watch())
        event.set()
        return orig(*a, **kw)

    setattr(obj, name, hook)
    return event, seen


def finish_both(rs, wa, wb):
    """Resolve two tickets of one window on two threads (one of them
    leads the drain); returns their answers."""
    got = {}

    def run(name, w):
        try:
            got[name] = rs.finish(w)
        except BaseException as e:  # noqa: BLE001 — handed to the test
            got[name] = e

    threads = [threading.Thread(target=run, args=a, daemon=True)
               for a in (("a", wa), ("b", wb))]
    for t in threads:
        t.start()
    return threads, got


def join_all(threads):
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive(), "a waiter of the drain never returned"


def test_a_group_with_pending_keys_is_gated_holding_nothing(pm):
    """Two groups in one drain: the covered one is captured (a reader
    count taken), the VC-less one finds its key pending.  Its gate's
    flush waits for readers to drain: it must find none, or the drain
    waits for itself."""
    rs = pm.read_server
    write(pm, "k1", 1)
    flushed(pm, "k1")
    write(pm, "k2", 2)                      # pending
    pm._val_cache.clear()
    wa = rs.stage([("k1", CK)], now_vc(pm))     # covered, ready
    wb = rs.stage([("k2", CK)], None)           # latest, not ready
    captures = []
    begin = pm.read_many_begin

    def counted(*a, **kw):
        cap = begin(*a, **kw)
        captures.append(cap is not None)
        return cap

    pm.read_many_begin = counted
    _ev, seen = entered(pm, "_wait_device_quiesce",
                        lambda: pm._dev_readers)
    threads, got = finish_both(rs, wa, wb)
    join_all(threads)
    assert got == {"a": {("k1", CK): 1}, "b": {("k2", CK): 2}}
    assert captures == [True, False, True]
    assert seen == [0] and pm._dev_readers == 0


def test_a_group_behind_a_prepared_transaction_is_gated_holding_nothing(
        pm):
    """A waiter whose clock runs ahead of this node's is not blocked
    when the drain classifies it; a transaction that prepares before
    the clock gets there blocks it at the capture.  The drain serves
    the other group, gives its count back, waits, and answers with the
    transaction's write."""
    rs = pm.read_server
    write(pm, "k1", 1)
    write(pm, "k2", 2)
    flushed(pm, "k1", "k2")
    ahead = VC({"dc1": pm.clock.now_us() + 150_000})
    wa = rs.stage([("k1", CK)], None)           # latest, ready
    wb = rs.stage([("k2", CK)], ahead)          # covered, not ready
    begin = pm.read_many_begin
    prepared = []

    def prepare_first(*a, **kw):
        if not prepared:
            snap = now_vc(pm)
            pm.stage_update(T, "k2", CK, 40)
            prepared.append((snap, pm.prepare(T, snap)))
        return begin(*a, **kw)

    pm.read_many_begin = prepare_first
    at_wait, seen = entered(pm, "_await_unprepared",
                            lambda: pm._dev_readers)
    threads, got = finish_both(rs, wa, wb)
    assert at_wait.wait(5), "the drain never waited for T"
    assert seen == [0], "the drain waits for T holding a reader count"
    snap, pt = prepared[0]
    assert pt < ahead.get_dc("dc1")
    pm.commit(T, pt, snap)
    join_all(threads)
    assert got == {"a": {("k1", CK): 1}, "b": {("k2", CK): 42}}
    assert pm._dev_readers == 0


def test_a_waiter_that_fails_revalidation_is_served_after_the_groups(pm):
    """tests/unit/test_read_serve.py's mid-window publish, with a
    second group in the drain: the older snapshot's own fold runs once
    every group's count is given back (its key is pending by then, so
    its gate flushes — under a held count that wait would never end)."""
    rs = pm.read_server
    c1 = write(pm, "k", 1)
    write(pm, "other", 5)
    flushed(pm, "k", "other")
    vc_lo = VC({"dc1": c1, "dc2": 100})
    vc_hi = VC({"dc1": c1, "dc2": 10_000})
    eff = get_type(CK).gen_downstream(
        ("increment", 500), None,
        DownstreamCtx(actor=("dc2", "t"), mint=lambda: ("dc2", 1)))
    begin = pm.read_many_begin
    published = []

    def begin_with_publish(*a, **kw):
        if not published:
            published.append(True)
            with pm._lock:
                pm._publish("k", CK, Payload(
                    key="k", type_name=CK, effect=eff, commit_dc="dc2",
                    commit_time=5000, snapshot_vc=VC({"dc2": 5000}),
                    txid=("dc2", "r1"), certified=True), None)
        return begin(*a, **kw)

    pm.read_many_begin = begin_with_publish
    _ev, seen = entered(pm, "_wait_device_quiesce",
                        lambda: pm._dev_readers)
    wa = rs.stage([("k", CK)], vc_lo)
    wb = rs.stage([("k", CK)], vc_hi)
    wc = rs.stage([("other", CK)], None)
    threads, got = finish_both(rs, wa, wb)
    join_all(threads)
    assert got == {"a": {("k", CK): 1}, "b": {("k", CK): 501}}
    assert rs.finish(wc) == {("other", CK): 5}
    assert seen and set(seen) == {0}
    assert pm._dev_readers == 0


def test_a_blocked_waiter_times_out_on_its_own_thread_with_the_message(
        pm):
    """Blocked when the drain classifies it, a waiter serves itself:
    ``pm.read_many``, the same driver with one request, and the
    message of old when the transaction never resolves."""
    rs = pm.read_server
    write(pm, "k1", 1)
    write(pm, "k2", 2)
    flushed(pm, "k1", "k2")
    snap = now_vc(pm)
    pm.stage_update(T, "k2", CK, 40)
    pm.prepare(T, snap)
    vc = now_vc(pm)
    wa = rs.stage([("k1", CK)], vc)
    wb = rs.stage([("k2", CK)], vc)
    t0 = time.monotonic()
    threads, got = finish_both(rs, wa, wb)
    join_all(threads)
    assert got["a"] == {("k1", CK): 1}
    assert isinstance(got["b"], TimeoutError)
    assert str(got["b"]) == "batched read blocked on prepared txn"
    assert 0.45 < time.monotonic() - t0 < 3.0
    assert wb.solo and pm._dev_readers == 0
