"""The device flusher's routine flush leaves the partition lock across
its dispatch and its fetch (``PartitionManager.flush_scheduled``): one
short hold takes the staged rows out as the plane's flight
(``_PlaneBase.begin_flight``), the flusher dispatches and fetches
holding no partition lock, and a second short hold settles the flight.

(a) a read of a key the flight carries waits, giving the lock back, and
answers with the flight's operations; (b) a read of another key of the
plane captures as soon as the dispatch has returned, while the fetch is
still out; (c) rows that overflow their ring settle through the same
retry path as in one hold, with the same values and evictions, with a
GC horizon, without one, and with no log; (d) an inline flush (a
read's gate, the commit path's backpressure, a key-directory grow) and
a GC fold issued while a flight is out wait for it and never dispatch
on the state it donates; (e) a threaded stress against a plain
history."""

import logging
import sys
import threading
import time

import pytest

from antidote_tpu import stats
from antidote_tpu.clocks import VC
from antidote_tpu.mat.device_plane import DevicePlane
from antidote_tpu.mat.ingest import IngestSettings
from antidote_tpu.oplog.partition import PartitionLog
from antidote_tpu.txn import manager
from antidote_tpu.txn.clock import HybridClock
from antidote_tpu.txn.manager import (CertificationError, DeviceFlusher,
                                      PartitionManager, read_many_fused)

TYPES = ("counter_pn", "set_aw")
_serial = iter(range(1, 10**9))


def make_pm(tmp_path, name="p0", flush_ops=4, lanes=8, key_capacity=1024,
            gc_ops=10**6, logged=True, coalesce_us=0, horizon=True):
    """A bare partition whose flushes are the test's to drive: commits
    stage (a scheduler that drops the request stands in for the
    flusher) until 4 x ``flush_ops`` rows force an inline flush.
    ``horizon``: GC folds at the partition's stable time
    (min_prepared), else at an empty horizon that folds nothing."""
    log = PartitionLog(str(tmp_path / f"{name}.log"), partition=0,
                       enabled=logged)
    pm = PartitionManager(
        0, "dc1", log, HybridClock(), read_wait_timeout=5.0,
        device_plane=DevicePlane(
            key_capacity=key_capacity, n_lanes=lanes, flush_ops=flush_ops,
            gc_ops=gc_ops,
            ingest_settings=IngestSettings(coalesce_us=coalesce_us)))
    pm.device.flush_scheduler = lambda plane: None
    if horizon:
        pm.stable_vc_source = lambda: VC({"dc1": pm.min_prepared()})
    return pm


@pytest.fixture
def pms(tmp_path, monkeypatch):
    """make_pm's partitions, closed after the test; the stable time is
    sampled afresh at every commit."""
    monkeypatch.setattr(manager, "_STABLE_REFRESH_S", -1.0)
    made = []

    def make(**kw):
        pm = make_pm(tmp_path, name=f"p{len(made)}", **kw)
        made.append(pm)
        return pm

    yield make
    for pm in made:
        pm.log.close()


def effect(pm, tn, key, i):
    """The i-th update of ``key``: an increment of i + 1, or the add of
    one of three elements with a fresh dot (a blind add: it observes
    nothing)."""
    if tn == "counter_pn":
        return i + 1
    return ("add", ((f"{key}-e{i % 3}", ("dc1", pm.clock.now_us()), ()),))


def fold(tn, effects):
    if tn == "counter_pn":
        return sum(effects)
    return {e for _k, entries in effects for e, _d, _o in entries}


def value(tn, state):
    return state if tn == "counter_pn" else set(state)


def commit(pm, tn, key, eff):
    """One single-partition transaction; returns its commit time."""
    txid = ("dc1", f"t{next(_serial)}")
    pm.stage_update(txid, key, tn, eff)
    return pm.single_commit(txid, VC({"dc1": pm.clock.now_us()}))


class Model:
    """Writes through a partition and keeps what each key must read."""

    def __init__(self, pm, tn):
        self.pm, self.tn, self.effects, self.n = pm, tn, {}, 0

    def write(self, key):
        eff = effect(self.pm, self.tn, key, self.n)
        self.n += 1
        commit(self.pm, self.tn, key, eff)
        self.effects.setdefault(key, []).append(eff)

    def expected(self, key):
        return fold(self.tn, self.effects.get(key, []))

    def read(self, key):
        pm = self.pm
        with pm._lock:
            pm._val_cache.clear()
        vc = VC({"dc1": pm.clock.now_us()})
        return value(self.tn, read_many_fused(
            [(pm, [(key, self.tn)])], vc)[(key, self.tn)])


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


def joined(t, box, what):
    t.join(timeout=20)
    assert not t.is_alive(), f"{what} never returned"
    assert "error" not in box, box.get("error")
    return box.get("value")


class Hold:
    """Stops the first call of ``plane.<name>`` until released, and
    counts how many calls of ``plane._dispatch_rows`` run at once (a
    dispatch while another is out would be one on a donated state)."""

    def __init__(self, plane, name):
        self.entered, self.release = threading.Event(), threading.Event()
        self.calls, self.live, self.most_live = 0, 0, 0
        self._lock = threading.Lock()
        orig_hold = getattr(plane, name)
        orig_dispatch = plane._dispatch_rows

        def held(*a, **kw):
            first = not self.entered.is_set()
            self.entered.set()
            if first:
                assert self.release.wait(20)
            return orig_hold(*a, **kw)

        def dispatch(*a, **kw):
            with self._lock:
                self.calls += 1
                self.live += 1
                self.most_live = max(self.most_live, self.live)
            try:
                if name == "_dispatch_rows":
                    return held(*a, **kw)
                return orig_dispatch(*a, **kw)
            finally:
                with self._lock:
                    self.live -= 1

        plane._dispatch_rows = dispatch
        if name != "_dispatch_rows":
            setattr(plane, name, held)


def start_flight(pm, plane, hold_at):
    """The flusher's work on ``plane`` on a thread of its own, stopped
    inside its dispatch or its fetch; returns (hold, thread, box)."""
    hold = Hold(plane, hold_at)
    t, box = in_thread(lambda: pm.flush_scheduled(plane))
    assert hold.entered.wait(20), "the flusher never reached the device"
    assert plane._flight is not None
    return hold, t, box


def split_count(outcome):
    return stats.registry.device_flush_split.value(outcome=outcome)


def waits():
    return stats.registry.device_flush_inflight_waits.value()


# ------------------------------------------------------ (a) a key aboard


@pytest.mark.parametrize("tn", TYPES)
@pytest.mark.parametrize("hold_at", ["_dispatch_rows", "_fetch_overflow"])
def test_a_read_of_a_key_in_flight_waits_and_sees_its_ops(pms, tn, hold_at):
    pm = pms()
    plane, m = pm.device.planes[tn], Model(pm, tn)
    for _ in range(4):
        m.write("k")
    assert "k" in plane.pending_keys
    clean, waited = split_count("clean"), waits()
    hold, flusher, fbox = start_flight(pm, plane, hold_at)
    assert "k" in plane._flight.keys and not plane.pending_keys
    reader, rbox = in_thread(lambda: m.read("k"))
    time.sleep(0.3)
    assert reader.is_alive(), "a read of a key in flight did not wait"
    # it waits giving the lock back: a commit of another key goes on
    m.write("other")
    hold.release.set()
    assert joined(reader, rbox, "the read") == m.expected("k")
    joined(flusher, fbox, "the flusher")
    assert plane._flight is None and hold.most_live == 1
    assert split_count("clean") == clean + 1
    assert waits() > waited


# ------------------------------------------ (b) another key, fetch still out


@pytest.mark.parametrize("tn", TYPES)
def test_a_read_of_another_key_captures_while_the_fetch_is_out(pms, tn):
    pm = pms()
    plane, m = pm.device.planes[tn], Model(pm, tn)
    m.write("j")
    with pm._lock:
        plane.flush("explicit")
    for _ in range(4):
        m.write("k")
    hold, flusher, fbox = start_flight(pm, plane, "_fetch_overflow")
    assert not plane._flight.donating, "the dispatch has returned"
    waited = waits()
    reader, rbox = in_thread(lambda: m.read("j"))
    assert joined(reader, rbox, "the read of another key") == \
        m.expected("j")
    assert plane._flight is not None, "it waited for the fetch"
    assert waits() == waited
    hold.release.set()
    joined(flusher, fbox, "the flusher")
    assert m.read("k") == m.expected("k")


# ------------------------------------------------- (c) overflow, both roads


def overflow_run(pm, tn, split):
    """Three keys, six rounds of one update each and a flush, on a
    plane of two lanes a key: the flusher's road or one hold."""
    plane, m = pm.device.planes[tn], Model(pm, tn)
    keys = ("a", "b", "c")
    for _ in range(6):
        for key in keys:
            m.write(key)
        if split:
            pm.flush_scheduled(plane)
        else:
            with pm._lock:
                plane.flush("rows")
    assert plane._flight is None and not plane.rows
    return ({key: m.read(key) for key in keys},
            {key: m.expected(key) for key in keys},
            set(pm.device.host_only))


@pytest.mark.parametrize("tn", TYPES)
@pytest.mark.parametrize("mode", ["horizon", "no_horizon", "unlogged"])
def test_an_overflow_settles_as_it_does_in_one_hold(pms, tn, mode):
    kw = dict(flush_ops=3, lanes=2, horizon=mode == "horizon",
              logged=mode != "unlogged")
    before = split_count("overflow")
    got_split, want, evicted_split = overflow_run(pms(**kw), tn, True)
    assert split_count("overflow") > before, "no flight overflowed"
    got_whole, _want, evicted_whole = overflow_run(pms(**kw), tn, False)
    assert got_split == got_whole
    assert evicted_split == evicted_whole
    if mode != "unlogged":
        # unlogged, both roads read the same and both miss the rows the
        # emergency fold retried and every later one until the next GC
        # fold: that fold raises the device base to _VC_INF, so the
        # inclusion mask takes every later op as folded (a fault of the
        # retry path itself, which both roads share; ROADMAP 3.14)
        assert got_split == want
    if mode == "no_horizon":
        assert evicted_split, "nothing folded, so the overflow evicts"
    else:
        assert not evicted_split


# ------------------------------------- (d) what must land the flight first


def _read_gate(pm, tn, key):
    pm.read_gate([(key, tn)], None, None, time.monotonic() + 20)


def _backpressure(m, plane):
    for _ in range(4 * plane.flush_ops):  # the last one flushes inline
        m.write("k")


def _grow(m, plane):
    for i in range(plane.capacity + 1):
        m.write(f"new{i}")


def _gc(pm, plane):
    with pm._lock:
        plane.gc(VC({"dc1": pm.min_prepared() - 1}))


@pytest.mark.parametrize("tn", TYPES)
@pytest.mark.parametrize("what", ["read_gate", "backpressure", "grow", "gc"])
def test_an_inline_flush_or_a_gc_waits_for_the_flight(pms, tn, what):
    pm = pms(key_capacity=8)
    plane, m = pm.device.planes[tn], Model(pm, tn)
    for _ in range(4):
        m.write("k")
    hold, flusher, fbox = start_flight(pm, plane, "_dispatch_rows")
    if what == "read_gate":
        m.write("k")  # pending again, beside the flight
    waited = waits()
    work = {"read_gate": lambda: _read_gate(pm, tn, "k"),
            "backpressure": lambda: _backpressure(m, plane),
            "grow": lambda: _grow(m, plane),
            "gc": lambda: _gc(pm, plane)}[what]
    t, box = in_thread(work)
    time.sleep(0.3)
    assert t.is_alive(), f"the {what} did not wait for the flight"
    assert hold.calls == 1, "a dispatch ran on the donated state"
    hold.release.set()
    joined(t, box, f"the {what}")
    joined(flusher, fbox, "the flusher")
    assert hold.most_live == 1 and waits() > waited
    assert plane._flight is None
    with pm._lock:
        plane.flush("explicit")
    for key in m.effects:
        assert m.read(key) == m.expected(key), key


# --------------------------------------------------------- (e) the stress


@pytest.mark.parametrize("tn", TYPES)
def test_writers_and_readers_against_a_plain_history(tmp_path, monkeypatch,
                                                     caplog, tn):
    """8 writers on keys of their own and 8 readers over every key, the
    real flusher thread, 4 operations a flush and a 200 us window, key
    capacity and lanes small enough to grow and to fold: every read at
    its snapshot equals the history's fold of the commits at or below
    it."""
    monkeypatch.setattr(manager, "_STABLE_REFRESH_S", -1.0)
    pm = make_pm(tmp_path, flush_ops=4, lanes=4, key_capacity=16,
                 gc_ops=32, coalesce_us=200)
    flusher = DeviceFlusher()
    pm.device.flush_scheduler = lambda plane: flusher.schedule(pm, plane)
    keys = [f"w{w}-{j}" for w in range(8) for j in range(4)]
    history, reads, failed = [], [], []
    stop = threading.Event()
    clean = split_count("clean")

    def writer(w):
        i = 0
        while not stop.is_set():
            key = f"w{w}-{i % 4}"
            eff = effect(pm, tn, key, i)
            try:
                ct = commit(pm, tn, key, eff)
            except CertificationError:
                continue
            except Exception as e:  # noqa: BLE001 — counted
                failed.append(e)
                continue
            history.append((key, ct, eff))
            i += 1

    def reader(r):
        i = r
        while not stop.is_set():
            items = [(keys[(i + d) % len(keys)], tn) for d in (0, 7, 13)]
            i += 1
            vc = VC({"dc1": pm.clock.now_us()})
            try:
                got = read_many_fused([(pm, items)], vc)
            except Exception as e:  # noqa: BLE001 — counted
                failed.append(e)
                continue
            reads.append((vc.get_dc("dc1"), got))

    threads = [threading.Thread(target=writer, args=(w,), daemon=True)
               for w in range(8)]
    threads += [threading.Thread(target=reader, args=(r,), daemon=True)
                for r in range(8)]
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)  # more hand-overs, more interleavings
    try:
        with caplog.at_level(logging.ERROR, logger="antidote_tpu"):
            for t in threads:
                t.start()
            time.sleep(3.0)
            stop.set()
            for t in threads:
                t.join(timeout=30)
                assert not t.is_alive()
            flusher.stop()
    finally:
        sys.setswitchinterval(switch)
        pm.log.close()
    assert not failed, failed[:3]
    assert not [r for r in caplog.records if r.levelno >= logging.ERROR]
    assert len(history) > 50 and len(reads) > 50
    by_key = {}
    for key, ct, eff in history:
        by_key.setdefault(key, []).append((ct, eff))
    for snap, got in reads:
        for (key, _tn), state in got.items():
            want = fold(tn, [e for ct, e in by_key.get(key, ())
                             if ct <= snap])
            assert value(tn, state) == want, (key, snap)
    assert split_count("clean") > clean
