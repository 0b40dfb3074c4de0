"""Coordinator tests against fake collaborators — the reference's
mocked-FSM tier (reference src/mock_partition.erl substituted into
clocksi_interactive_coord via TEST macros, tests at
src/clocksi_interactive_coord.erl:1150-1265): no ring, no disk, no
store — a fake partition whose behavior is keyed by the key name,
exercising the coordinator's state machine alone.

Behavior keys: "conflict*" fails certification at prepare;
"crash_prepare*" raises a non-certification error; "read_fail*" fails
the read.
"""

import pytest

from antidote_tpu.clocks import VC
from antidote_tpu.config import Config
from antidote_tpu.crdt import get_type
from antidote_tpu.hooks import HookRegistry
from antidote_tpu.txn.coordinator import (
    Coordinator,
    Transaction,
    TransactionAborted,
    TxnState,
)
from antidote_tpu.txn.manager import CertificationError


class FakePartition:
    """mock_partition equivalent: canned success/abort/crash keyed by
    the key's name; records every call for assertions."""

    def __init__(self, partition: int):
        self.partition = partition
        self.calls = []
        self.staged = {}
        self.prepare_time = 1000 + partition  # distinct per partition

    # -- coordinator surface ------------------------------------------
    def stage_update(self, txid, key, type_name, effect):
        self.calls.append(("stage", txid, key))
        self.staged.setdefault(txid, []).append((key, type_name, effect))

    def stage_group(self, txid, ops, snapshot_vc=None):
        # the coordinator stages a call's effects once per partition
        for key, type_name, effect in ops:
            self.stage_update(txid, key, type_name, effect)

    def read_with_writeset(self, key, type_name, snapshot_vc, txid,
                           own_effects, exact_state=False):
        self.calls.append(("read", key))
        if str(key).startswith("read_fail"):
            raise RuntimeError("mocked read failure")
        state = get_type(type_name).new()
        if own_effects:
            cls = get_type(type_name)
            for eff in own_effects:
                state = cls.update(eff, state)
        return state

    def read_many(self, items, snapshot_vc, txid=None):
        # the coordinator's batched read path (own effects are applied
        # by the coordinator, so the fake returns fresh state only)
        out = {}
        for key, type_name in items:
            self.calls.append(("read", key))
            if str(key).startswith("read_fail"):
                raise RuntimeError("mocked read failure")
            out[(key, type_name)] = get_type(type_name).new()
        return out

    def prepare(self, txid, snapshot_vc, certify=True):
        self.calls.append(("prepare", txid))
        for key, _t, _e in self.staged.get(txid, []):
            if str(key).startswith("conflict"):
                raise CertificationError(f"write-write conflict on {key}")
            if str(key).startswith("crash_prepare"):
                raise RuntimeError("mocked vnode crash")
        return self.prepare_time

    def commit(self, txid, commit_time, snapshot_vc, certified=True,
               redeem=True):
        self.calls.append(("commit", txid, commit_time))
        self.staged.pop(txid, None)
        if not redeem:    # the 2PC commit redeems after every partition
            return txid
        self.redeem(txid)

    def redeem(self, txid):
        self.calls.append(("redeem", txid))

    def single_commit(self, txid, snapshot_vc, certify=True):
        self.prepare(txid, snapshot_vc, certify)
        ct = self.prepare_time
        self.commit(txid, ct, snapshot_vc)
        self.calls.append(("single_commit", txid))
        return ct

    def abort(self, txid):
        self.calls.append(("abort", txid))
        self.staged.pop(txid, None)

    def min_prepared(self):
        return 10**15


class FakeClock:
    def __init__(self):
        self.t = 10**15

    def now_us(self):
        self.t += 1
        return self.t


class FakeNode:
    """Node surface the coordinator drives, with fake partitions."""

    def __init__(self, n_partitions=4):
        self.dc_id = "dcM"
        self.config = Config(n_partitions=n_partitions,
                             clock_wait_timeout_s=0.2)
        self.clock = FakeClock()
        self.hooks = HookRegistry()
        self.partitions = [FakePartition(p) for p in range(n_partitions)]
        self.bcounter_mgr = None
        self.stable_vc = lambda: VC({self.dc_id: self.clock.t})
        self.wait_hook = lambda: None
        self.mint_dot = lambda: ("dcM", self.clock.now_us())
        from antidote_tpu.txn.node import TxnGate

        self.txn_gate = TxnGate()

    def partition_index(self, key):
        if isinstance(key, int):
            return key % len(self.partitions)
        return sum(str(key).encode()) % len(self.partitions)

    def partition_of(self, key):
        return self.partitions[self.partition_index(key)]

    from antidote_tpu.txn.node import Node as _N
    normalize_bound = staticmethod(_N.normalize_bound)
    normalize_update = staticmethod(_N.normalize_update)

    def gen_downstream(self, cls, op, state, ctx, key=None, bucket=None):
        return cls.gen_downstream(op, state, ctx)


@pytest.fixture
def node():
    return FakeNode()


@pytest.fixture
def coord(node):
    return Coordinator(node)


def _keys_on(node, p):
    """n distinct keys all mapping to partition p."""
    return [k for k in range(100) if node.partition_index(k) == p]


class TestEmptyPrepare:
    """reference empty_prepare_test: committing with no updates
    succeeds and the causal clock is the snapshot."""

    def test_commit_empty(self, coord):
        tx = coord.start_transaction()
        cvc = coord.commit_transaction(tx)
        assert tx.state is TxnState.COMMITTED
        assert cvc == tx.snapshot_vc
        # no partition was ever touched
        for pm in coord.node.partitions:
            assert pm.calls == []


class TestSinglePartition:
    def test_single_commit_fast_path(self, coord, node):
        keys = _keys_on(node, 2)
        tx = coord.start_transaction()
        coord.update_objects(
            tx, [((keys[0], "counter_pn"), "increment", 1),
                 ((keys[1], "counter_pn"), "increment", 2)])
        cvc = coord.commit_transaction(tx)
        pm = node.partitions[2]
        assert ("single_commit", tx.txid) in pm.calls
        # no 2PC prepare/commit round on other partitions
        for other in node.partitions:
            if other is not pm:
                assert other.calls == []
        assert cvc.get_dc("dcM") == pm.prepare_time


class TestTwoPhaseCommit:
    """reference update_multi_success: commit time = max prepare time,
    every touched partition gets commit(ct)."""

    def test_commit_time_is_max_prepare(self, coord, node):
        k0 = _keys_on(node, 0)[0]
        k3 = _keys_on(node, 3)[0]
        tx = coord.start_transaction()
        coord.update_objects(
            tx, [((k0, "counter_pn"), "increment", 1),
                 ((k3, "counter_pn"), "increment", 1)])
        cvc = coord.commit_transaction(tx)
        ct = max(node.partitions[0].prepare_time,
                 node.partitions[3].prepare_time)
        assert cvc.get_dc("dcM") == ct
        for p in (0, 3):
            assert ("commit", tx.txid, ct) in node.partitions[p].calls

    def test_certification_conflict_aborts_all(self, coord, node):
        ok_key = _keys_on(node, 0)[0]
        tx = coord.start_transaction()
        coord.update_objects(
            tx, [((ok_key, "counter_pn"), "increment", 1),
                 (("conflict_k", "counter_pn"), "increment", 1)])
        with pytest.raises(TransactionAborted, match="conflict"):
            coord.commit_transaction(tx)
        assert tx.state is TxnState.ABORTED
        for p in tx.partitions:
            assert ("abort", tx.txid) in node.partitions[p].calls

    def test_non_certification_crash_also_aborts(self, coord, node):
        ok_key = _keys_on(node, 0)[0]
        tx = coord.start_transaction()
        coord.update_objects(
            tx, [((ok_key, "counter_pn"), "increment", 1),
                 (("crash_prepare_k", "counter_pn"), "increment", 1)])
        with pytest.raises(TransactionAborted, match="prepare failed"):
            coord.commit_transaction(tx)
        assert tx.state is TxnState.ABORTED
        for p in tx.partitions:
            assert ("abort", tx.txid) in node.partitions[p].calls

    def test_commit_round_failure_is_not_an_abort(self, coord, node):
        """Post-decision failures must surface as outcome-unknown, not
        abort: one partition already committed durably."""
        from antidote_tpu.txn.coordinator import CommitOutcomeUnknown

        k0 = _keys_on(node, 0)[0]
        k3 = _keys_on(node, 3)[0]

        def failing_commit(txid, ct, snap, certified=True, redeem=True):
            raise OSError("disk full")

        node.partitions[3].commit = failing_commit
        tx = coord.start_transaction()
        coord.update_objects(
            tx, [((k0, "counter_pn"), "increment", 1),
                 ((k3, "counter_pn"), "increment", 1)])
        with pytest.raises(CommitOutcomeUnknown, match="commit decided"):
            coord.commit_transaction(tx)
        assert tx.state is TxnState.UNKNOWN
        # partition 0 committed; neither partition was told to abort
        assert ("commit", tx.txid,
                max(node.partitions[0].prepare_time,
                    node.partitions[3].prepare_time)) \
            in node.partitions[0].calls
        # and its durability ticket was still redeemed
        assert ("redeem", tx.txid) in node.partitions[0].calls
        for pm in node.partitions:
            assert ("abort", tx.txid) not in pm.calls


class TestReads:
    """reference read_fail / read_success mocked cases."""

    def test_read_success_and_your_writes(self, coord):
        tx = coord.start_transaction()
        coord.update_objects(tx, [(("rk", "counter_pn"), "increment", 5)])
        assert coord.read_objects(tx, [("rk", "counter_pn")]) == [5]

    def test_read_failure_aborts(self, coord, node):
        tx = coord.start_transaction()
        coord.update_objects(tx, [(("rk", "counter_pn"), "increment", 1)])
        with pytest.raises(TransactionAborted, match="read failed"):
            coord.read_objects(tx, [("read_fail_k", "counter_pn")])
        assert tx.state is TxnState.ABORTED
        # staged partitions were told to abort
        for p in tx.partitions:
            assert ("abort", tx.txid) in node.partitions[p].calls

    def test_aborted_txn_rejects_further_ops(self, coord):
        tx = coord.start_transaction()
        coord.abort_transaction(tx)
        with pytest.raises(TransactionAborted):
            coord.read_objects(tx, [("k", "counter_pn")])
        with pytest.raises(TransactionAborted):
            coord.update_objects(tx, [(("k", "counter_pn"), "increment", 1)])


class TestDownstreamFailure:
    """reference downstream_fail mocked case: the op is valid but
    downstream generation fails -> abort."""

    def test_downstream_failure_aborts(self, coord, node):
        tx = coord.start_transaction()
        with pytest.raises(TransactionAborted, match="downstream"):
            coord.update_objects(
                tx, [(("bk", "counter_b"), "decrement", (5, "dcM"))])
        assert tx.state is TxnState.ABORTED


class TestHookFailure:
    def test_pre_hook_failure_aborts(self, coord, node):
        def bad_hook(key, type_name, op):
            raise ValueError("rejected by hook")

        node.hooks.register_pre_hook("guarded", bad_hook)
        tx = coord.start_transaction()
        with pytest.raises(TransactionAborted, match="pre-commit hook"):
            coord.update_objects(
                tx, [(("k", "counter_pn", "guarded"), "increment", 1)])
        assert tx.state is TxnState.ABORTED
