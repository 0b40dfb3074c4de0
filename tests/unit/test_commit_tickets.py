"""A durable 2PC commit over local partitions stages every partition's
commit record and takes every durability ticket before it redeems any:
the locked halves (``PartitionManager.commit(..., redeem=False)``) in
partition order, then the unlocked halves (``PartitionManager.redeem``).
What the redemptions met is counted as
``antidote_log_sync_redeemed_total{outcome}``."""

from __future__ import annotations

import threading

import pytest

from antidote_tpu import stats
from antidote_tpu.clocks import VC
from antidote_tpu.config import Config
from antidote_tpu.oplog.log import DurableLog, GroupSettings
from antidote_tpu.oplog.partition import PartitionLog
from antidote_tpu.txn.coordinator import CommitOutcomeUnknown, TxnState
from antidote_tpu.txn.manager import PartitionManager
from antidote_tpu.txn.node import Node

OUTCOMES = ("covered", "led", "woken")
#: txids no other test records spans under (the tracer's ring outlives
#: a test)
TX = {n: ("redeemed", n) for n in range(1, 5)}


def durable_node(tmp_path, after: bool, n: int = 4) -> Node:
    return Node(dc_id="dc1", config=Config(
        n_partitions=n, sync_log=True, publish_after_durable=after,
        device_store=False, data_dir=str(tmp_path / "data")))


def one_key_a_partition(node) -> list:
    """A counter key on each partition, in partition order."""
    keys, seen = [], set()
    for k in range(1000):
        p = node.partition_index(k)
        if p not in seen:
            seen.add(p)
            keys.append((p, k))
        if len(seen) == len(node.partitions):
            break
    return [k for _p, k in sorted(keys)]


def begun_transaction(node, keys, amount=1):
    coord = node.coordinator
    tx = coord.start_transaction()
    coord.update_objects(tx, [((k, "counter_pn"), "increment", amount)
                              for k in keys])
    assert len(tx.partitions) == len(keys)
    return coord, tx


def watch(monkeypatch) -> list:
    """Every commit append and every redemption, in order: ("append",
    partition, txid), ("wait", partition, txid, ticket) and, as the
    wait returns, ("covered", partition, txid, whether the synced
    watermark covers the ticket)."""
    events = []
    real_append = PartitionLog.append_commit
    real_wait = PartitionLog.wait_durable

    def append_commit(self, dc, txid, *args, **kwargs):
        rec = real_append(self, dc, txid, *args, **kwargs)
        events.append(("append", self.partition, txid))
        return rec

    def wait_durable(self, ticket, txid=None):
        events.append(("wait", self.partition, txid, ticket))
        real_wait(self, ticket, txid=txid)
        events.append(("covered", self.partition, txid,
                       self.log.queue_stats()["synced_end"] >= ticket))

    monkeypatch.setattr(PartitionLog, "append_commit", append_commit)
    monkeypatch.setattr(PartitionLog, "wait_durable", wait_durable)
    return events


# ------------------------------------------------- the commit's two halves


@pytest.mark.parametrize("after", [False, True],
                         ids=["publish_first", "publish_after_durable"])
def test_a_2pc_commit_stages_every_record_before_it_waits(
        tmp_path, monkeypatch, after):
    node = durable_node(tmp_path, after)
    keys = one_key_a_partition(node)
    coord, tx = begun_transaction(node, keys, amount=3)
    pms = [node.partitions[p] for p in tx.partitions]
    events = watch(monkeypatch)
    at_first_wait = {}
    real_wait = PartitionLog.wait_durable

    def first_wait_sees(self, ticket, txid=None):
        if not at_first_wait:
            at_first_wait.update(
                defer=[pm._defer_unpublished for pm in pms],
                staged=[tx.txid in pm._staged for pm in pms],
                ends=[pm.log.log.durability_ticket() for pm in pms])
        real_wait(self, ticket, txid=txid)

    monkeypatch.setattr(PartitionLog, "wait_durable", first_wait_sees)
    commit_vc = coord.commit_transaction(tx)
    assert tx.state is TxnState.COMMITTED
    kinds = [e[0] for e in events]
    # four records appended, then four tickets redeemed, in the
    # transaction's partition order, each covered when its wait returned
    assert kinds == ["append"] * 4 + ["wait", "covered"] * 4
    order = list(tx.partitions)
    assert [e[1] for e in events if e[0] == "append"] == order
    assert [e[1] for e in events if e[0] == "wait"] == order
    tickets = [e[3] for e in events if e[0] == "wait"]
    assert all(t is not None and t > 0 for t in tickets)
    assert all(e[3] for e in events if e[0] == "covered")
    # every ticket existed before the first wait: nothing was appended
    # to any log after it, so each partition's end was its ticket
    assert at_first_wait["ends"] == tickets
    # the record and its effects appear in one hold; under
    # publish_after_durable the publish waits for the ticket
    assert at_first_wait["staged"] == [after] * 4
    assert at_first_wait["defer"] == [int(after)] * 4
    for pm, ticket in zip(pms, tickets):
        assert pm.log.log.queue_stats()["synced_end"] >= ticket
        assert pm._defer_unpublished == 0
        assert tx.txid not in pm.prepared and tx.txid not in pm._staged
    for k in keys:
        assert node.partition_of(k).value_snapshot(
            k, "counter_pn", commit_vc) == 3
    node.close()


@pytest.mark.parametrize("after", [False, True],
                         ids=["publish_first", "publish_after_durable"])
def test_a_first_half_that_fails_still_redeems_the_ones_before_it(
        tmp_path, monkeypatch, after):
    node = durable_node(tmp_path, after)
    keys = one_key_a_partition(node)
    coord, tx = begun_transaction(node, keys, amount=5)
    pms = [node.partitions[p] for p in tx.partitions]
    events = watch(monkeypatch)
    real_commit = PartitionManager.commit

    def commit(self, txid, *args, **kwargs):
        if self is pms[2]:
            raise OSError("disk full")
        return real_commit(self, txid, *args, **kwargs)

    monkeypatch.setattr(PartitionManager, "commit", commit)
    with pytest.raises(CommitOutcomeUnknown, match="disk full"):
        coord.commit_transaction(tx)
    assert tx.state is TxnState.UNKNOWN
    # the first two partitions: record appended, ticket redeemed and
    # covered, deferred publish run, nothing left behind
    first_two = [pm.partition for pm in pms[:2]]
    assert [e[:2] for e in events] == [
        ("append", first_two[0]), ("append", first_two[1]),
        ("wait", first_two[0]), ("covered", first_two[0]),
        ("wait", first_two[1]), ("covered", first_two[1])]
    assert all(e[3] for e in events if e[0] == "covered")
    for pm in pms[:2]:
        assert pm._defer_unpublished == 0
        assert tx.txid not in pm.prepared and tx.txid not in pm._staged
        key = keys[pm.partition]
        assert pm.value_snapshot(key, "counter_pn") == 5
    # the staging stopped at the failure: the third and fourth
    # partitions hold their prepared entries (the outcome is unknown,
    # so nothing is aborted)
    for pm in pms[2:]:
        assert tx.txid in pm.prepared
        assert pm._defer_unpublished == 0
    node.close()


def test_a_wait_that_fails_does_not_skip_the_others(tmp_path,
                                                    monkeypatch):
    node = durable_node(tmp_path, True)
    keys = one_key_a_partition(node)
    coord, tx = begun_transaction(node, keys)
    pms = [node.partitions[p] for p in tx.partitions]
    real_wait = PartitionLog.wait_durable

    def wait(self, ticket, txid=None):
        if self is pms[1].log:
            raise TimeoutError("durability ticket never covered")
        real_wait(self, ticket, txid=txid)

    monkeypatch.setattr(PartitionLog, "wait_durable", wait)
    with pytest.raises(CommitOutcomeUnknown, match="never covered"):
        coord.commit_transaction(tx)
    for pm in pms:
        assert pm._defer_unpublished == 0
        assert tx.txid not in pm.prepared and tx.txid not in pm._staged
    node.close()


# ------------------------------------------------- the redemption counter


def redeemed() -> dict:
    return {o: stats.registry.log_sync_redeemed.value(outcome=o)
            for o in OUTCOMES}


def counted_since(before: dict) -> dict:
    now = redeemed()
    return {o: now[o] - before[o] for o in OUTCOMES}


def test_each_redemption_counts_what_it_met(tmp_path):
    """``led``: nothing drained the ticket yet, the committer drains;
    ``covered``: an earlier drain already covered it; ``woken``: a
    drain was in flight and covered it while the committer slept."""
    from antidote_tpu.obs.spans import tracer

    plog = PartitionLog(str(tmp_path / "p0"), partition=0,
                        sync_on_commit=True, backend="python",
                        group=GroupSettings(enabled=True, group_us=0))
    old_rate, tracer.sample_rate = tracer.sample_rate, 1.0
    try:
        before = redeemed()
        plog.append_commit("dc1", TX[1], 5, VC())
        t1 = plog.commit_ticket()
        plog.append_commit("dc1", TX[2], 6, VC())
        t2 = plog.commit_ticket()
        plog.wait_durable(t2, txid=TX[2])    # drains both records
        plog.wait_durable(t1, txid=TX[1])    # already covered
        assert counted_since(before) == {"covered": 1, "led": 1,
                                         "woken": 0}
        (led,) = tracer.spans(txid=TX[2], name="log_sync_wait")
        (covered,) = tracer.spans(txid=TX[1], name="log_sync_wait")
        assert (led.args["led"], led.args["covered"]) == (True, False)
        assert (covered.args["led"], covered.args["covered"]) == \
            (False, True)

        # a drain held in its fsync; a second committer arrives and
        # sleeps on it
        log: DurableLog = plog.log
        entered, release = threading.Event(), threading.Event()
        real_sync = log._backend_sync

        def held_sync(io):
            entered.set()
            assert release.wait(10)
            real_sync(io)

        log._backend_sync = held_sync
        plog.append_commit("dc1", TX[3], 7, VC())
        t3 = plog.commit_ticket()
        before = redeemed()
        leader = threading.Thread(
            target=plog.wait_durable, args=(t3,), kwargs={"txid": TX[3]})
        leader.start()
        assert entered.wait(10)
        follower = threading.Thread(
            target=plog.wait_durable, args=(t3,), kwargs={"txid": TX[4]})
        follower.start()
        while log._sync_waiters < 1:     # the follower sleeps
            threading.Event().wait(0.001)
        release.set()
        leader.join(10)
        follower.join(10)
        assert counted_since(before) == {"covered": 0, "led": 1,
                                         "woken": 1}
        (woken,) = tracer.spans(txid=TX[4], name="log_sync_wait")
        assert (woken.args["led"], woken.args["covered"]) == \
            (False, False)
    finally:
        tracer.sample_rate = old_rate
        plog.close()


def prepared_pair(node, keys, n):
    """Transaction ``n`` staged and prepared on the partitions of
    ``keys`` by hand; returns (txid, commit time, snapshot)."""
    txid = (node.dc_id, n)
    svc = VC({node.dc_id: node.clock.now_us()})
    for k in keys:
        node.partition_of(k).stage_update(txid, k, "counter_pn", 1)
    ct = max(node.partition_of(k).prepare(txid, svc, certify=False)
             for k in keys)
    return txid, ct, svc


@pytest.mark.parametrize("redeem", ["together", "one_at_a_time"])
def test_staged_together_the_later_tickets_are_found_covered(
        tmp_path, redeem):
    """Two transactions over the same two partitions.  Staged together
    (every locked half, then every redemption), the second committer's
    tickets are covered by the first's drains.  The control redeems
    each ticket right after its record is staged, one partition at a
    time, as a commit did before: every redemption then drains."""
    node = durable_node(tmp_path, False, n=2)
    keys = one_key_a_partition(node)
    pms = [node.partition_of(k) for k in keys]
    a = prepared_pair(node, keys, 1)
    b = prepared_pair(node, keys, 2)
    before = redeemed()
    if redeem == "together":
        staged = [(pm, pm.commit(*txn, certified=False, redeem=False))
                  for txn in (a, b) for pm in pms]
        for pm, st in staged:
            pm.redeem(st)
    else:
        for txn in (a, b):
            for pm in pms:
                pm.commit(*txn, certified=False)
    got = counted_since(before)
    if redeem == "together":
        assert got == {"covered": 2, "led": 2, "woken": 0}
    else:
        assert got == {"covered": 0, "led": 4, "woken": 0}
    for k, pm in zip(keys, pms):
        assert pm.value_snapshot(k, "counter_pn") == 2
    node.close()
