"""The dependency gate's three implementations — the host head-walk,
the resident device ring and the legacy repack — against ONE table of
cases (queues and stamps in, watermarks and applied set out), and
against each other on random queue shapes: all three must compute
identical applied sets, orders, and final clocks.

The rule the table spells (interdc/dep.py module doc, PERF.md §7.2):
an origin's watermark is the newest stamp received from it, lowered to
the smallest commit time still queued from it, less one — never an
applied transaction's commit time, never a blocked head's."""

from collections import deque

import numpy as np
import pytest

from antidote_tpu.clocks import VC
from antidote_tpu.interdc.dep import DependencyGate
from antidote_tpu.interdc.wire import InterDcTxn


class FakePM:
    def __init__(self):
        self.applied = []

    def apply_remote(self, records, dc_id, ts, snapshot_vc):
        self.applied.append((dc_id, ts))


def make_txn(origin, ts, snapshot, ping=False):
    return InterDcTxn(
        dc_id=origin, partition=0, prev_log_opid=0,
        snapshot_vc=None if ping else VC(snapshot), timestamp=ts,
        records=[] if ping else ["r"])


#: the three implementations as (batch_threshold, device_ring)
IMPLS = {"host": (10**9, True), "ring": (0, True), "repack": (0, False)}


def make_gate(impl, now=10**9):
    threshold, device_ring = IMPLS[impl]
    pm = FakePM()
    gate = DependencyGate(pm, "dc_self", now_us=lambda: now,
                          batch_threshold=threshold,
                          device_ring=device_ring, adapt=False,
                          coalesce_us=0)
    return gate, pm


def run(gate, queues):
    """Feed queues (origin -> txns in stream order; a ping anywhere in
    one is that origin's stamp) straight into the gate's state —
    enqueue() itself triggers passes — then ONE process_queues."""
    for origin, txns in queues.items():
        real = [t for t in txns if not t.is_ping()]
        if real:
            gate.queues[origin] = deque(real)
        for t in txns:
            if t.is_ping():
                gate.stamps[origin] = max(t.timestamp,
                                          gate.stamps.get(origin, 0))
    gate.process_queues()
    return {o: len(q) for o, q in gate.queues.items() if q}


def T(origin, ts, **deps):
    return make_txn(origin, ts, deps)


def P(origin, ts):
    return make_txn(origin, ts, {}, ping=True)


#: name -> (queues, expected applied set, expected watermarks, left
#: queued).  Watermarks list every origin worth a look; 0 = never
#: raised.
RULE_TABLE = {
    # a stamp alone: complete only BELOW it (a commit at exactly the
    # stamp can still be in flight)
    "stamp_is_exclusive": (
        {"b": [P("b", 500)]}, [], {"b": 499}, {}),
    # an applied txn promises nothing: with no stamp the origin's
    # watermark stays where it was, though its whole queue applied
    "apply_without_stamp_raises_nothing": (
        {"b": [T("b", 100), T("b", 200)]},
        [("b", 100), ("b", 200)], {"b": 0}, {}),
    # ...so a dependency on that origin's time waits for its stamp
    "dependant_waits_for_the_stamp_not_the_apply": (
        {"a": [T("a", 150, b=200)], "b": [T("b", 100), T("b", 200)]},
        [("b", 100), ("b", 200)], {"a": 0, "b": 0}, {"a": 1}),
    "stamp_after_applies_releases_dependant": (
        {"a": [T("a", 150, b=200)],
         "b": [T("b", 100), T("b", 200), P("b", 201)]},
        [("a", 150), ("b", 100), ("b", 200)], {"b": 200}, {}),
    # the log-order case of PERF.md §7.2: ct 100 arrives and applies
    # before ct 90 has come; the stamp that rode with it is the
    # origin's min-prepared, at most 90
    "later_commit_applied_first_stays_uncovered": (
        {"b": [T("b", 100), P("b", 90)]},
        [("b", 100)], {"b": 89}, {}),
    # every queued txn bounds the watermark, not the head alone: the
    # head (300) is blocked, 250 sits behind it
    "every_queued_txn_bounds_not_the_head_alone": (
        {"b": [T("b", 300, zz=10**12), T("b", 250), P("b", 400)]},
        [], {"b": 249}, {"b": 2}),
    # a blocked head WITHOUT a stamp raises nothing (the reference's
    # blocked-head rule is gone)
    "blocked_head_without_stamp_raises_nothing": (
        {"b": [T("b", 300, zz=10**12)]}, [], {"b": 0}, {"b": 1}),
    # FIFO: a ready txn behind a blocked head waits
    "fifo_blocks_later_ready_txns": (
        {"a": [T("a", 100, zz=10**12), T("a", 200), P("a", 300)]},
        [], {"a": 99}, {"a": 2}),
    # two origins whose heads wait on each other: the stamps queued
    # behind the blocked txns unblock both (sound form of the
    # blocked-head rule), and the applies then let the watermarks rise
    # to the stamps
    "stamps_behind_blocked_heads_break_a_cross_block": (
        {"dcA": [T("dcA", 61, dcB=50), T("dcA", 70, dcB=50),
                 P("dcA", 71)],
         "dcB": [T("dcB", 55, dcA=60), T("dcB", 66, dcA=60),
                 P("dcB", 67)]},
        [("dcA", 61), ("dcA", 70), ("dcB", 55), ("dcB", 66)],
        {"dcA": 70, "dcB": 66}, {}),
    # the same heads with no stamps stay blocked: nothing the receiver
    # holds proves either stream complete below its head
    "cross_block_without_stamps_stays": (
        {"dcA": [T("dcA", 61, dcB=50)], "dcB": [T("dcB", 55, dcA=60)]},
        [], {"dcA": 0, "dcB": 0}, {"dcA": 1, "dcB": 1}),
    # a stamp unblocks another origin's head (cascade over rounds)
    "stamp_unblocks_other_origin": (
        {"a": [T("a", 150, b=500)], "b": [P("b", 501)]},
        [("a", 150)], {"b": 500}, {}),
    # a dependency at exactly the stamp stays gated until the commit
    # record itself and a stamp above it
    "dependency_at_exactly_the_stamp_waits": (
        {"a": [T("a", 150, b=500)], "b": [P("b", 500)]},
        [], {"b": 499}, {"a": 1}),
    "commit_at_the_stamp_then_newer_stamp_releases": (
        {"a": [T("a", 150, b=500)],
         "b": [P("b", 500), T("b", 500), P("b", 501)]},
        [("a", 150), ("b", 500)], {"b": 500}, {}),
}


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("case", RULE_TABLE)
def test_rule_table(case, impl):
    queues, want_applied, want_wm, want_left = RULE_TABLE[case]
    gate, pm = make_gate(impl)
    left = run(gate, {o: list(q) for o, q in queues.items()})
    assert sorted(pm.applied) == sorted(want_applied)
    for origin in queues:  # per-origin apply order is FIFO
        seq = [t for o, t in pm.applied if o == origin]
        assert seq == [t.timestamp for t in queues[origin]
                       if not t.is_ping() and (origin, t.timestamp)
                       in want_applied]
    assert {o: gate.applied_vc.get_dc(o) for o in want_wm} == want_wm
    assert left == want_left


def random_scenario(seed, n_origins=6, q_len=8):
    """Queues whose txns depend on other origins' later commits, so
    applying cascades across origins (the fixpoint case); commit times
    within a queue are NOT sorted (log order), and stamps stand at
    random places."""
    rng = np.random.default_rng(seed)
    origins = [f"dc{i}" for i in range(n_origins)]
    queues = {}
    for oi, origin in enumerate(origins):
        txns = []
        base = 100 * (oi + 1)
        for p in range(q_len):
            ts = base + 50 * p + int(rng.integers(-60, 10))
            if rng.random() < 0.3:
                txns.append(make_txn(origin, ts, {}, ping=True))
                continue
            snap = {}
            for dep_oi in rng.choice(n_origins, size=2, replace=False):
                # mostly on origins before this one, so the cascade
                # runs origin by origin over many rounds; now and then
                # on a later one, which may knot
                if dep_oi == oi or (dep_oi > oi and rng.random() < 0.8):
                    continue
                # a time the other origin's queue reaches partway
                # through
                snap[origins[dep_oi]] = 100 * (dep_oi + 1) - 61 + 50 * int(
                    rng.integers(0, q_len))
            snap[origin] = ts - 1
            txns.append(make_txn(origin, ts, snap))
        if rng.random() < 0.8:
            # the stream's newest stamp: above all it sent, so the
            # origin's watermark climbs as its queue drains
            txns.append(make_txn(origin, base + 50 * q_len, {}, ping=True))
        queues[origin] = txns
    return queues


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("ring", [True, False])
def test_batched_matches_host_walk(seed, ring):
    """Both batched forms — the ISSUE-3 resident ring and the legacy
    repack — must match the host walk bit-for-bit."""
    queues = random_scenario(seed)
    host_gate, host_pm = make_gate("host")
    dev_gate, dev_pm = make_gate("ring" if ring else "repack")
    left_host = run(host_gate, {o: list(q) for o, q in queues.items()})
    left_dev = run(dev_gate, {o: list(q) for o, q in queues.items()})
    assert host_pm.applied, "a scenario that applies nothing proves nothing"
    assert sorted(host_pm.applied) == sorted(dev_pm.applied)
    # per-origin apply order is FIFO in both
    for origin in queues:
        host_seq = [t for o, t in host_pm.applied if o == origin]
        dev_seq = [t for o, t in dev_pm.applied if o == origin]
        assert host_seq == dev_seq
    assert left_host == left_dev
    assert host_gate.applied_vc == dev_gate.applied_vc


def test_blocked_txn_stays_queued_until_dependency_applies():
    gate, pm = make_gate("ring")
    # a's txn depends on b@200, which is b's second txn; b's stamp
    # above it says b's stream is complete there
    a1 = make_txn("a", 150, {"b": 200})
    b1 = make_txn("b", 100, {})
    b2 = make_txn("b", 200, {})
    run(gate, {"a": [a1], "b": [b1, b2, P("b", 201)]})
    assert ("a", 150) in pm.applied
    assert pm.applied.index(("b", 200)) < pm.applied.index(("a", 150))
    assert gate.pending() == 0


def test_fifo_blocks_later_ready_txns():
    gate, pm = make_gate("ring")
    # a's head can never apply; a's second txn is ready but must wait
    blocked = make_txn("a", 100, {"zz": 10**12})
    ready = make_txn("a", 200, {})
    run(gate, {"a": [blocked, ready]})
    assert pm.applied == []
    assert gate.pending() == 2


def test_pings_advance_clock_and_unblock():
    gate, pm = make_gate("ring")
    a1 = make_txn("a", 150, {"b": 500})
    ping_b = make_txn("b", 501, {}, ping=True)
    run(gate, {"a": [a1], "b": [ping_b]})
    assert pm.applied == [("a", 150)]
    assert gate.applied_vc.get_dc("b") == 500
    assert gate.pending() == 0


@pytest.mark.parametrize("threshold", [0, 10**9])
def test_ping_advance_is_exclusive(threshold):
    """A heartbeat's contract is "no FUTURE txn commits with a SMALLER
    time" — completeness only BELOW the stamp.  Clock-SI picks commit
    time = max(prepare times), so the max-prepare partition's
    min_prepared EQUALS a pending commit's time and its ping can
    outrun the commit record; an inclusive advance would let a causal
    reader pass the stable wait and miss the txn (the reference
    carries this µs race, inter_dc_dep_vnode.erl:122-125; caught live
    by tests/multidc/test_ring_placement.py under load)."""
    impl = "ring" if threshold == 0 else "host"
    gate, pm = make_gate(impl)
    # a ping stamped exactly at a still-in-flight commit's time...
    gate.enqueue(P("b", 500))
    # ...must NOT claim completeness AT 500
    assert gate.applied_vc.get_dc("b") == 499
    # a dependency on b at exactly 500 stays gated until the real txn
    gate2, pm2 = make_gate(impl)
    gate2.enqueue_batch([T("a", 150, b=500), P("b", 500)])
    assert pm2.applied == []
    assert gate2.pending() == 1
    # the commit record itself (ts=500) applies, and still releases
    # nothing: applied at 500 is not "complete at 500" (a smaller
    # commit time may be behind it in the log)
    gate2.enqueue(T("b", 500))
    assert pm2.applied == [("b", 500)]
    assert gate2.applied_vc.get_dc("b") == 499
    assert gate2.pending() == 1
    # the origin's next stamp — its min-prepared, above 500 once that
    # commit left its prepared table — does
    gate2.enqueue(P("b", 501))
    assert pm2.applied == [("b", 500), ("a", 150)]
    assert gate2.applied_vc.get_dc("b") == 500
    assert gate2.pending() == 0


@pytest.mark.parametrize("ring", [True, False])
def test_blocked_head_advances_clock_breaks_cross_block(ring):
    """Two origins whose heads each need a time only the other's
    blocked stream can provide (the 3-DC variant is the chaos test's
    partition-window race).  The reference breaks the knot by raising
    a blocked head's origin to ts-1 (src/inter_dc_dep_vnode.erl:
    137-143), which presumes commit-time order on the stream.  The
    sound form: the stamp that rode in BEHIND the blocked txns counts
    at once, bounded by every txn queued, so each origin stands at
    (smallest queued commit time) - 1 — enough for the other's head.
    Exercised through BOTH gating paths via the batch threshold, by
    enqueue as the SubBuf delivers (txns, then the frame's stamp)."""
    for threshold in (4, 100):  # device fixpoint / host head-walk
        pm = FakePM()
        g = DependencyGate(pm, "dc0", lambda: 10 ** 9,
                           batch_threshold=threshold, device_ring=ring,
                           coalesce_us=0)
        g.enqueue_batch([T("dcA", 61, dcB=50), T("dcA", 70, dcB=50),
                         P("dcA", 71)])
        # alone, dcA's frame applies nothing and claims 60: its own
        # smallest queued commit time bounds the stamp
        assert pm.applied == [] and g.applied_vc.get_dc("dcA") == 60
        g.enqueue_batch([T("dcB", 55, dcA=60), T("dcB", 66, dcA=60),
                         P("dcB", 67)])
        assert len(pm.applied) == 4, (threshold, pm.applied)
        # applying the txn that bounded the watermark lets it rise to
        # the stamp (exclusive), not to a commit time
        assert g.applied_vc.get_dc("dcA") == 70
        assert g.applied_vc.get_dc("dcB") == 66
        assert not g.pending()
        # without the stamps the same heads stay blocked
        g2 = DependencyGate(FakePM(), "dc0", lambda: 10 ** 9,
                            batch_threshold=threshold, device_ring=ring,
                            coalesce_us=0)
        g2.enqueue_batch([T("dcA", 61, dcB=50), T("dcA", 70, dcB=50)])
        g2.enqueue_batch([T("dcB", 55, dcA=60), T("dcB", 66, dcA=60)])
        assert g2.pm.applied == [] and g2.pending() == 4


def test_visibility_is_recorded_when_the_watermark_passes():
    """vis_lag / interdc_visible mark the moment a causal read at the
    commit clock can see the txn: the watermark passing its commit
    time, not its apply."""
    from antidote_tpu import stats

    gate, pm = make_gate("host")
    hist = stats.registry.vis_lag
    n0 = hist.count(dc="dc_self", peer="b")
    t = T("b", 100)
    t.trace_ctx = (1, 1000)
    gate.enqueue(t)
    assert pm.applied == [("b", 100)]
    assert hist.count(dc="dc_self", peer="b") == n0  # applied, unseen
    gate.enqueue(P("b", 100))   # stamp AT the commit time: exclusive
    assert hist.count(dc="dc_self", peer="b") == n0
    gate.enqueue(P("b", 101))
    assert hist.count(dc="dc_self", peer="b") == n0 + 1
    st = gate.queue_stats()["stamps"]["b"]
    assert st["stamp"] == 101 and st["age_us"] == 0
