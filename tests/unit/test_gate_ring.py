"""Device-resident gate ring (ISSUE 3): incremental appends, the
coalescing window, growth/compaction re-layouts, host-path interleave
retires, partial-wave recovery, and the GATE_* counter economy —
everything the amortization story rests on beyond the bit-for-bit
equivalence test_dep_gate.py already pins."""

from collections import deque

import pytest

from antidote_tpu import stats
from antidote_tpu.clocks import VC
from antidote_tpu.interdc.dep import GATE_DISPATCH_KINDS, DependencyGate
from antidote_tpu.interdc.wire import InterDcTxn
from antidote_tpu.txn.manager import PartitionRetired


class Clock:
    """Controllable µs clock: coalescing windows open and close only
    when the test says so."""

    def __init__(self, t=10**9):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, us):
        self.t += us


class FakePM:
    def __init__(self):
        self.applied = []

    def apply_remote(self, records, dc_id, ts, snapshot_vc):
        self.applied.append((dc_id, ts))


class RetiringPM(FakePM):
    """Raises PartitionRetired for marked txns until healed
    (mid-handoff) — the partial-wave abort path."""

    def __init__(self, poison):
        super().__init__()
        self.poison = set(poison)

    def heal(self):
        self.poison.clear()

    def apply_remote(self, records, dc_id, ts, snapshot_vc):
        if (dc_id, ts) in self.poison:
            raise PartitionRetired(f"handoff {dc_id}@{ts}")
        super().apply_remote(records, dc_id, ts, snapshot_vc)


def txn(origin, ts, snapshot, ping=False):
    return InterDcTxn(
        dc_id=origin, partition=0, prev_log_opid=0,
        snapshot_vc=None if ping else VC(snapshot), timestamp=ts,
        records=[] if ping else ["r"])


#: the stamp that lets origin z's commit at 5000 count as applied
STAMP_Z = txn("z", 5001, {}, ping=True)


def make_gate(pm=None, clock=None, **kw):
    pm = pm or FakePM()
    clock = clock or Clock()
    kw.setdefault("batch_threshold", 0)
    kw.setdefault("coalesce_us", 0)
    gate = DependencyGate(pm, "dc_self", now_us=clock, **kw)
    return gate, pm, clock


def dispatches(kind=None):
    reg = stats.registry
    if kind is not None:
        return reg.gate_dispatches.value(kind=kind)
    return sum(reg.gate_dispatches.value(kind=k)
               for k in GATE_DISPATCH_KINDS)


def test_incremental_append_beats_repack_on_h2d_bytes():
    """A backlog receiving one new head per delivery: the legacy path
    re-uploads the WHOLE queue every pass (O(n^2) bytes over the
    stream), the ring uploads each txn once plus a per-dispatch clock
    (O(n)) — the core amortization claim, measured via the real
    GATE_* counters."""
    n = 64
    streams = {}
    for ring in (True, False):
        # adapt=False pins the batched path: this measures the two
        # batched implementations, not the learner's routing
        gate, pm, clock = make_gate(device_ring=ring, adapt=False)
        h2d0 = stats.registry.gate_h2d_bytes.value()
        # every txn blocks on origin z's ts=5000 commit, so the
        # backlog only grows while the stream arrives
        for i in range(n):
            gate.enqueue(txn(f"dc{i}", 100 + i, {"z": 5000}))
            clock.advance(60_000)  # outlive the backlog-skip window
        # the commit and the stamp that says z is complete at 5000
        gate.enqueue_batch([txn("z", 5000, {}), STAMP_Z])
        gate.process_queues()
        assert gate.pending() == 0
        assert len(pm.applied) == n + 1
        streams[ring] = stats.registry.gate_h2d_bytes.value() - h2d0
    assert streams[True] * 4 <= streams[False], streams


def test_coalescing_window_batches_a_burst():
    gate, pm, clock = make_gate(batch_threshold=1, coalesce_us=1000,
                                adapt=False)
    coal0 = stats.registry.gate_coalesced.value()
    fix0 = dispatches("fixpoint")
    gate.enqueue(txn("a", 100, {}))           # opens the window
    for i in range(9):                        # burst inside the window
        gate.enqueue(txn(f"b{i}", 200 + i, {}))
    assert stats.registry.gate_coalesced.value() - coal0 == 9
    assert len(pm.applied) == 1               # staged, not admitted
    clock.advance(2000)                       # window closed
    gate.enqueue(txn("c", 300, {}))
    assert len(pm.applied) == 11              # one dispatch, whole burst
    assert gate.pending() == 0
    # exactly two fixpoints: the opener and the burst-drainer
    assert dispatches("fixpoint") - fix0 == 2


def test_explicit_process_queues_bypasses_coalescing():
    gate, pm, clock = make_gate(batch_threshold=1, coalesce_us=10**9,
                                adapt=False)
    gate.enqueue(txn("a", 100, {}))
    gate.enqueue(txn("b", 200, {}))           # coalesced forever...
    assert len(pm.applied) == 1
    gate.process_queues()                     # ...until asked directly
    assert len(pm.applied) == 2


def test_ring_grows_past_initial_capacity():
    gate, pm, clock = make_gate(ring_capacity=8, adapt=False)
    n = 40
    for i in range(n):
        gate.enqueue(txn(f"dc{i}", 100 + i, {"z": 5000}))
        clock.advance(60_000)
    assert gate._ring.cap >= n
    gate.enqueue_batch([txn("z", 5000, {}), STAMP_Z])
    gate.process_queues()
    assert gate.pending() == 0 and len(pm.applied) == n + 1
    assert dispatches("gather") > 0  # at least one growth re-layout


def test_ring_compacts_after_backlog_drains():
    gate, pm, clock = make_gate(ring_capacity=8, adapt=False)
    for i in range(40):
        gate.enqueue(txn(f"dc{i}", 100 + i, {"z": 5000}))
        clock.advance(60_000)
    gate.enqueue_batch([txn("z", 5000, {}), STAMP_Z])
    gate.process_queues()
    grown = gate._ring.cap
    assert grown > 8
    g0 = dispatches("gather")
    # the next (small) wave syncs: dead slots >> compact threshold
    gate.enqueue(txn("late", 9000, {}))
    gate.process_queues()
    assert gate._ring.cap == 8, (grown, gate._ring.cap)
    assert dispatches("gather") > g0
    assert ("late", 9000) in pm.applied


def test_host_walk_interleave_retires_ring_rows():
    """The adaptive picker can route consecutive passes down different
    paths: txns the HOST walk admits while sitting in the ring must be
    retired on device, never re-admitted."""
    gate, pm, clock = make_gate(adapt=False)
    # two txns blocked on z, synced into the ring by a batched pass
    gate.queues["a"] = deque([txn("a", 100, {"z": 5000})])
    gate.queues["b"] = deque([txn("b", 200, {"z": 5000})])
    gate._process_batched()
    assert gate._ring.n_live == 2 and pm.applied == []
    # z's commit lands, with the stamp that covers it, and a HOST
    # pass drains everything
    gate.queues["z"] = deque([txn("z", 5000, {})])
    gate.stamps["z"] = STAMP_Z.timestamp
    gate._process_host()
    assert sorted(pm.applied) == [("a", 100), ("b", 200), ("z", 5000)]
    r0 = dispatches("retire")
    # the next batched pass reconciles: retire scatter, no re-apply
    assert gate._process_batched() is False
    assert dispatches("retire") == r0 + 1
    assert gate._ring.n_live == 0
    assert len(pm.applied) == 3
    # and the ring is still usable afterwards
    gate.enqueue(txn("a", 6000, {}))
    gate.process_queues()
    assert ("a", 6000) in pm.applied and gate.pending() == 0


def test_partition_retired_aborts_wave_and_recovers():
    pm = RetiringPM(poison=[("b", 200)])
    gate, pm, clock = make_gate(pm=pm, adapt=False)
    gate.queues["a"] = deque([txn("a", 100, {})])
    gate.queues["b"] = deque([txn("b", 200, {})])
    gate.queues["c"] = deque([txn("c", 300, {})])
    gate.stamps["b"] = 201
    gate.process_queues()
    # the poisoned txn stays queued; the fixpoint clock, which counted
    # it applied (b at its stamp, 200), was NOT adopted over the
    # unapplied remainder
    assert ("b", 200) not in pm.applied
    assert gate.pending() >= 1
    assert gate.applied_vc.get_dc("b") < 200
    pm.heal()
    gate.process_queues()
    assert sorted(pm.applied) == [("a", 100), ("b", 200), ("c", 300)]
    assert gate.pending() == 0
    # the stamp less one, now that nothing queued bounds it
    assert gate.applied_vc.get_dc("b") == 200
    # a and c never stamped: applying their txns promised nothing
    assert gate.applied_vc.get_dc("a") == gate.applied_vc.get_dc("c") == 0


def test_ping_rows_flow_through_ring():
    """A heartbeat is no ring row any more: the gate keeps its stamp
    and the fixpoint takes the stamps as a vector, so a stamp of an
    origin with nothing queued still releases another's head."""
    gate, pm, clock = make_gate(adapt=False)
    gate.enqueue(txn("a", 150, {"b": 500}))
    assert gate._ring.n_live == 1 and pm.applied == []
    gate.enqueue(txn("b", 501, {}, ping=True))
    assert gate._ring.n_live == 0 and "b" in gate._ring.cols
    assert pm.applied == [("a", 150)]
    assert gate.applied_vc.get_dc("b") == 500  # exclusive ping advance
    assert gate.pending() == 0


def test_counters_and_amortization_gauge():
    reg = stats.registry
    adm0 = reg.gate_admitted_batched.value()
    gate, pm, clock = make_gate(adapt=False)
    for i in range(16):
        gate.enqueue(txn(f"dc{i}", 100 + i, {}))
        clock.advance(60_000)
    admitted = reg.gate_admitted_batched.value() - adm0
    assert admitted == 16
    total = dispatches()
    assert total > 0
    assert reg.gate_admitted_per_dispatch.value() == pytest.approx(
        reg.gate_admitted_batched.value() / total)
    # D2H stays lean: an all-admitted pass fetches count+mask+rounds+
    # clock; a no-op pass only count+clock — both are bounded by the
    # ring size, not the history
    assert reg.gate_d2h_bytes.value() > 0
