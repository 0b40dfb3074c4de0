"""``PartitionManager.min_prepared()`` and ``has_prepared()`` take no
partition lock (PR 26): they read a minimum that the writers of the
prepared table publish, and the stable-time promise (no transaction
commits below a returned value without being visible already) is kept
by the order of two clock draws against one store and one load.

(a) the values, (b) no lock, (c) every interleaving of one reader with
one preparing writer, driven through the clock, and the same schedule
catching a reader that loads before it draws, (d) a threaded hammer,
(e) a snapshot's span tree holds no ``pm_lock_wait``."""

import bisect
import itertools
import random
import sys
import threading
import time

import pytest

from antidote_tpu.api import AntidoteTPU
from antidote_tpu.clocks import VC
from antidote_tpu.config import Config
from antidote_tpu.obs.spans import tracer
from antidote_tpu.oplog.partition import PartitionLog
from antidote_tpu.txn.clock import HybridClock
from antidote_tpu.txn.coordinator import TxnProperties
from antidote_tpu.txn.manager import CertificationError, PartitionManager

T1, T2 = ("dc1", 1), ("dc1", 2)


def make_pm(tmp_path, clock=None):
    log = PartitionLog(str(tmp_path / "p0.log"), partition=0)
    return PartitionManager(0, "dc1", log, clock or HybridClock())


@pytest.fixture
def pm(tmp_path):
    pm = make_pm(tmp_path)
    yield pm
    pm.log.close()


# ------------------------------------------------------------- (a) values


def test_empty_table_reads_a_time_not_above_now(pm):
    before = pm.clock.now_us()
    v = pm.min_prepared()
    assert before < v < pm.clock.now_us()
    assert not pm.has_prepared()


def test_two_prepared_read_the_smaller_prepare_time(pm):
    pt1 = pm.prepare(T1, VC())
    pt2 = pm.prepare(T2, VC())
    assert pt1 < pt2
    assert pm.min_prepared() == pt1
    assert pm.has_prepared()


def test_after_the_older_commits_the_younger_is_the_minimum(pm):
    pt1 = pm.prepare(T1, VC())
    pt2 = pm.prepare(T2, VC())
    pm.commit(T1, pt1, VC())
    assert pm.min_prepared() == pt2
    pm.commit(T2, pt2, VC())
    assert not pm.has_prepared()
    assert pm.min_prepared() > pt2


def test_after_an_abort_the_other_is_the_minimum(pm):
    pt1 = pm.prepare(T1, VC())
    pt2 = pm.prepare(T2, VC())
    pm.abort(T1)
    assert pm.min_prepared() == pt2 > pt1
    pm.abort(T2)
    assert not pm.has_prepared()
    # aborting what was never prepared leaves the published state alone
    pm.abort(("dc1", 3))
    assert not pm.has_prepared()


def test_single_commit_leaves_the_table_empty(pm):
    pm.stage_update(T1, "k", "counter_pn", 1)
    ct = pm.single_commit(T1, VC())
    assert pm.prepared == {} and not pm.has_prepared()
    assert ct < pm.min_prepared() <= pm.clock.now_us()


def test_a_refused_prepare_publishes_nothing(pm):
    """Certification fails before the floor goes out: a refused
    transaction never looks prepared."""
    pm.stage_update(T1, "k", "counter_pn", 1)
    ct = pm.single_commit(T1, VC())
    pm.stage_update(T2, "k", "counter_pn", 1)
    with pytest.raises(CertificationError):
        pm.prepare(T2, VC({"dc1": ct - 1}))
    assert not pm.has_prepared() and pm._min_prep is None
    pm.abort(T2)


def test_the_published_minimum_follows_the_table_at_every_change(pm):
    """Under the lock the published value is the table's minimum, or
    None for an empty table, after each of the four writers."""
    rng = random.Random(26)
    live = {}
    for i in range(200):
        txid = ("dc1", 100 + i)
        roll = rng.random()
        if roll < 0.5 or not live:
            live[txid] = pm.prepare(txid, VC())
        elif roll < 0.6:
            pm.single_commit(txid, VC())
        else:
            gone = rng.choice(sorted(live))
            pt = live.pop(gone)
            pm.commit(gone, pt, VC()) if roll < 0.85 else pm.abort(gone)
        want = min(live.values()) if live else None
        assert pm._min_prep == want
        assert {t: pt for t, (pt, _k) in pm.prepared.items()} == live


# ------------------------------------------------------------ (b) no lock


def test_readers_return_while_another_thread_holds_the_lock(pm):
    pt = pm.prepare(T1, VC())
    holding, release = threading.Event(), threading.Event()
    let_go_early = []

    def hold():
        with pm._lock:
            holding.set()
            let_go_early.append(not release.wait(1.0))

    t = threading.Thread(target=hold)
    t.start()
    try:
        assert holding.wait(5)
        # the fastest of a few tries: a reader behind the lock takes the
        # holder's second every time, a loaded machine only now and then
        took = 1.0
        for _ in range(5):
            t0 = time.monotonic()
            got = (pm.min_prepared(), pm.has_prepared())
            took = min(took, time.monotonic() - t0)
    finally:
        release.set()
        t.join(5)
    assert not t.is_alive()
    assert got == (pt, True)
    assert took < 0.05
    assert let_go_early == [False]  # the lock was held throughout


# ---------------------------------------------------------- (c) the window


def _nothing():
    pass


class ScriptedClock(HybridClock):
    """A HybridClock whose ``now_us`` runs the calling thread's hooks
    before and after its n-th draw (outside the clock's own lock), so
    a test can stop a thread between two lines of the code under
    test."""

    def __init__(self):
        super().__init__()
        self._mine = threading.local()

    def script(self, hooks):
        """``{(n, "before" | "after"): callable}`` for this thread."""
        self._mine.hooks, self._mine.n = dict(hooks), 0

    def now_us(self):
        hooks = getattr(self._mine, "hooks", None)
        if not hooks:
            return super().now_us()
        self._mine.n += 1
        n = self._mine.n
        hooks.pop((n, "before"), _nothing)()
        t = super().now_us()
        hooks.pop((n, "after"), _nothing)()
        return t


class Gate:
    """A hook that reports its thread arrived and holds it there."""

    def __init__(self):
        self.reached, self.go = threading.Event(), threading.Event()

    def __call__(self):
        self.reached.set()
        assert self.go.wait(5)


def shipped_reader(pm):
    return pm.min_prepared()


def load_first_reader(pm):
    """The ordering the docstring warns of: the published value is
    loaded before the clock is drawn."""
    published = pm._min_prep
    now = pm.clock.now_us()
    return now if published is None else published


#: where the reader stops between its two steps: the shipped reader
#: after its draw (its load follows), the naive one before its draw
#: (its load is behind it)
READER_STOPS = {shipped_reader: "after", load_first_reader: "before"}

#: the writer's points, in the order prepare() passes them with an
#: empty table: 0 not begun, 1 floor drawn but not stored, 2 floor
#: stored and ``pt`` not drawn, 3 ``pt`` drawn and the table not yet
#: written, 4 returned
SCHEDULES = [(p, q) for p in range(5) for q in range(p, 5)]


def interleave(tmp_path, reader, first_at, second_at):
    """Run ``reader``'s first step with the writer stopped at point
    ``first_at`` and its second at ``second_at``; (value, pt)."""
    pm = make_pm(tmp_path, ScriptedClock())
    gates = {1: Gate(), 2: Gate(), 3: Gate()}
    rgate = Gate()
    out = {}

    def write():
        pm.clock.script({(1, "after"): gates[1], (2, "before"): gates[2],
                         (2, "after"): gates[3]})
        out["pt"] = pm.prepare(T1, VC())

    def read():
        pm.clock.script({(1, READER_STOPS[reader]): rgate})
        out["v"] = reader(pm)

    w = threading.Thread(target=write)
    r = threading.Thread(target=read)
    at = 0

    def advance(to):
        nonlocal at
        while at < to:
            if at == 0:
                w.start()
            else:
                gates[at].go.set()
            at += 1
            if at < 4:
                assert gates[at].reached.wait(5)
            else:
                w.join(5)
                assert not w.is_alive()

    try:
        advance(first_at)
        r.start()
        assert rgate.reached.wait(5)
        advance(second_at)
        rgate.go.set()
        r.join(5)
        assert not r.is_alive()
        advance(4)
    finally:
        for g in (*gates.values(), rgate):
            g.go.set()
        pm.log.close()
    return out["v"], out["pt"]


@pytest.mark.parametrize("first_at,second_at", SCHEDULES)
def test_no_interleaving_reads_above_the_prepare_time(
        tmp_path, first_at, second_at):
    """The reader draws with the writer at ``first_at`` and loads with
    it at ``second_at`` — while the writer holds the partition lock."""
    v, pt = interleave(tmp_path, shipped_reader, first_at, second_at)
    assert v <= pt
    if first_at == 4:
        assert v == pt  # prepared and seen: exactly its prepare time


def test_the_same_schedules_catch_a_reader_that_loads_first(tmp_path):
    """Load before the floor is stored, draw after ``pt`` is drawn: a
    stable time above a pending prepare.  The schedules that pass the
    shipped reader are a test only if they fail this one."""
    above = set()
    for i, (first_at, second_at) in enumerate(SCHEDULES):
        d = tmp_path / str(i)
        d.mkdir()
        v, pt = interleave(d, load_first_reader, first_at, second_at)
        if v > pt:
            above.add((first_at, second_at))
    assert above == {(p, q) for p, q in SCHEDULES if p <= 1 and q >= 3}


# ------------------------------------------------------------ (d) hammer


def test_hammer_no_read_passes_a_pending_prepare(tmp_path):
    """Four threads prepare and commit, abort or single-commit; two
    read.  A read may not return a value above the prepare time of any
    transaction whose commit or abort had not begun when the read
    ended: that transaction was invisible for the whole of the read.
    (One whose commit ended inside the read may be passed: it is
    visible by then, and the locked reader passed it too.)"""
    pm = make_pm(tmp_path)
    seq = itertools.count()  # next() is atomic under the interpreter lock
    ends = []      # (seq at which the txn's commit / abort began, its pt)
    real_commit = pm.commit

    def commit(txid, commit_time, snapshot_vc, certified=True):
        ends.append((next(seq), commit_time))
        real_commit(txid, commit_time, snapshot_vc, certified)

    pm.commit = commit  # single_commit reaches it through self
    rounds, stop, errors = 750, threading.Event(), []
    reads = [[], []]  # per reader: (seq when the read had ended, value)

    def writer(w):
        rng = random.Random(2600 + w)
        try:
            for i in range(rounds):
                txid = ("dc1", w * rounds + i)
                roll = rng.random()
                if roll < 0.3:
                    pm.single_commit(txid, VC())
                    continue
                pt = pm.prepare(txid, VC())
                if rng.random() < 0.5:
                    time.sleep(0)  # let a reader in while it is pending
                if roll < 0.9:
                    pm.commit(txid, pt, VC())
                else:
                    ends.append((next(seq), pt))
                    pm.abort(txid)
        except BaseException as e:  # noqa: BLE001 — reported below
            errors.append(e)

    def reader(mine):
        try:
            while not stop.is_set():
                v = pm.min_prepared()
                if len(mine) < 200_000:
                    mine.append((next(seq), v))
        except BaseException as e:  # noqa: BLE001 — reported below
            errors.append(e)

    threads = [threading.Thread(target=writer, args=(w,))
               for w in range(4)]
    readers = [threading.Thread(target=reader, args=(m,)) for m in reads]
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in readers + threads:
            t.start()
        for t in threads:
            t.join(120)
        stop.set()
        for t in readers:
            t.join(10)
    finally:
        stop.set()
        sys.setswitchinterval(switch)
    assert not any(t.is_alive() for t in threads + readers)
    assert errors == []
    assert len(ends) == 4 * rounds and not pm.has_prepared()
    # smallest pt among the transactions ending after each point
    ends.sort()
    began = [s for s, _pt in ends]
    least_after = [0] * (len(ends) + 1)
    least_after[-1] = float("inf")
    for i in range(len(ends) - 1, -1, -1):
        least_after[i] = min(ends[i][1], least_after[i + 1])
    checked = 0
    for mine in reads:
        assert len(mine) > 100
        for read_ended, v in mine:
            bound = least_after[bisect.bisect_right(began, read_ended)]
            assert v <= bound, (read_ended, v, bound)
            checked += bound != float("inf")
    assert checked > 100  # reads did overlap pending transactions
    pm.log.close()


# -------------------------------------------------------------- (e) spans


def test_a_snapshot_waits_for_no_partition_lock(tmp_path):
    db = AntidoteTPU(config=Config(n_partitions=2, stable_ttl_s=0.0),
                     data_dir=str(tmp_path / "data"))
    saved = tracer.sample_rate
    tracer.clear()
    tracer.sample_rate = 1.0
    holding, release = threading.Barrier(3), threading.Event()

    def hold(pm):
        with pm._lock:
            holding.wait(5)
            release.wait(2.0)

    holders = [threading.Thread(target=hold, args=(pm,))
               for pm in db.node.partitions]
    out = {}

    def snapshot():
        with tracer.root("pb_request", "wire", 1, 1):
            out["snap"] = db.node.coordinator.snapshot_for(
                None, TxnProperties())

    try:
        for t in holders:
            t.start()
        holding.wait(5)
        before = db.node.clock.now_us()
        s = threading.Thread(target=snapshot)
        s.start()
        s.join(1.0)
        finished = not s.is_alive()
        spans = list(tracer.spans())
    finally:
        release.set()
        for t in holders:
            t.join(5)
        tracer.sample_rate = saved
        tracer.clear()
        db.close()
    assert finished, "snapshot_for stood behind a partition lock"
    assert out["snap"].get_dc(db.node.dc_id) > before
    by_id = {sp.span_id: sp for sp in spans}
    snap = [sp for sp in spans if sp.name == "txn_snapshot"]
    assert len(snap) == 1

    def under_snapshot(sp):
        while sp.parent_id is not None:
            sp = by_id[sp.parent_id]
            if sp is snap[0]:
                return True
        return False

    assert [sp.name for sp in spans if under_snapshot(sp)] == []
    assert "pm_lock_wait" not in {sp.name for sp in spans}
