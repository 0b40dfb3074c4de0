"""Group-commit durable-log plane (ISSUE 9): staged batch appends,
ticket-based durability off the partition lock, window/leader drains,
on-disk byte-compatibility with the legacy per-record writer, and the
refcounted close guard that moved fsync out of the handle lock.

The crash-recovery differential is the plane's load-bearing test:
every byte prefix of a group-written log must recover to exactly the
whole-record prefix a legacy-written twin yields — the batched writer
changes WHO writes, never what lands on disk.
"""

import os
import threading
import time

import pytest

from antidote_tpu import stats
from antidote_tpu.clocks import VC
from antidote_tpu.config import Config
from antidote_tpu.oplog.log import (
    DurableLog,
    GroupSettings,
    log_group_from_config,
    _NativeBackend,
)
from antidote_tpu.oplog.partition import PartitionLog

BACKENDS = ["python"] + (["native"] if _NativeBackend.load() else [])


@pytest.fixture(params=BACKENDS)
def backend(request):
    return request.param


def grp(**kw):
    kw.setdefault("group_us", 200)
    kw.setdefault("group_records", 64)
    return GroupSettings(**kw)


# ---------------------------------------------------------------- settings


def test_group_from_config_is_the_single_factory():
    s = log_group_from_config(Config(log_group=False, log_group_us=7,
                                     log_group_records=9))
    assert (s.enabled, s.group_us, s.group_records) == (False, 7, 9)
    assert log_group_from_config(None) == GroupSettings()


def test_knob_false_routes_legacy_path(tmp_path, backend):
    """GroupSettings(enabled=False) keeps the exact per-record write
    path: nothing ever stages and sync happens where the caller runs
    it — the bench baseline contract."""
    log = DurableLog(str(tmp_path / "leg"), backend=backend,
                     group=grp(enabled=False))
    assert not log.group_active
    log.append(b"one")
    assert log._staged == []  # wrote through immediately
    # wait_durable is a no-op on the legacy path
    assert log.wait_durable(10**9) == {"led": False, "records": 0}
    log.close()


def test_node_routes_config_knob(tmp_path):
    from antidote_tpu.txn.node import Node

    node = Node("dcK", Config(n_partitions=1, device_store=False,
                              log_group=False),
                data_dir=str(tmp_path / "off"))
    assert not node.partitions[0].log.log.group_active
    node.close()
    node2 = Node("dcK2", Config(n_partitions=1, device_store=False,
                                log_group=True, log_group_us=123),
                 data_dir=str(tmp_path / "on"))
    dlog = node2.partitions[0].log.log
    assert dlog.group_active and dlog._group.group_us == 123
    node2.close()


# ------------------------------------------------------------ byte layout


def test_group_and_legacy_logs_are_byte_identical(tmp_path, backend):
    payloads = [f"record-{i}".encode() * (1 + i % 3) for i in range(40)]
    g = DurableLog(str(tmp_path / "g"), backend=backend, group=grp())
    offs_g = [g.append(p) for p in payloads]
    g.sync()
    g.close()
    l = DurableLog(str(tmp_path / "l"), backend=backend)
    offs_l = [l.append(p) for p in payloads]
    l.sync()
    l.close()
    assert offs_g == offs_l
    assert (tmp_path / "g").read_bytes() == (tmp_path / "l").read_bytes()


def test_append_batch_matches_singles(tmp_path, backend):
    payloads = [f"b{i}".encode() for i in range(10)]
    a = DurableLog(str(tmp_path / "a"), backend=backend)
    first = a.append_batch(payloads)
    assert first == 0
    a.flush()
    assert [b for _o, b in a.scan()] == payloads
    a.close()
    b = DurableLog(str(tmp_path / "b"), backend=backend)
    for p in payloads:
        b.append(p)
    b.flush()
    b.close()
    assert (tmp_path / "a").read_bytes() == (tmp_path / "b").read_bytes()


def test_crash_recovery_differential(tmp_path, backend):
    """Kill mid-group: truncate the group-written file at EVERY byte
    boundary; recovery must keep exactly the whole-record prefix the
    legacy twin defines and drop the torn tail."""
    payloads = [f"r{i}-".encode() + bytes([i]) * (i % 5) for i in range(12)]
    gpath = str(tmp_path / "g")
    g = DurableLog(gpath, backend=backend, group=grp())
    g.append_batch(payloads)
    g.sync()
    g.close()
    full = (tmp_path / "g").read_bytes()
    # whole-record prefixes from the legacy writer
    legacy_prefixes = {0: b""}
    lp = str(tmp_path / "l")
    l = DurableLog(lp, backend=backend)
    for p in payloads:
        l.append(p)
        l.flush()
        legacy_prefixes[os.path.getsize(lp)] = (tmp_path / "l").read_bytes()
    l.close()
    assert (tmp_path / "l").read_bytes() == full
    for cut in range(len(full) + 1):
        tpath = tmp_path / "t"
        tpath.write_bytes(full[:cut])
        rec = DurableLog(str(tpath), backend=backend)
        end = rec.end_offset()
        got = (b for _o, b in rec.scan())
        got = list(got)
        rec.close()
        # recovered prefix is the largest whole-record legacy prefix
        # at or below the cut
        expect_size = max(s for s in legacy_prefixes if s <= cut)
        assert end == expect_size, f"cut={cut}"
        assert tpath.read_bytes() == legacy_prefixes[expect_size]
        n_whole = sum(1 for s in sorted(legacy_prefixes) if 0 < s <= cut)
        assert got == payloads[:n_whole], f"cut={cut}"


# ------------------------------------------------------- durability plane


def test_solo_committer_drains_immediately(tmp_path, backend):
    log = DurableLog(str(tmp_path / "solo"), backend=backend,
                     group=grp(group_us=10**6))  # a HUGE window
    t0 = time.perf_counter()
    for i in range(5):
        log.append(f"c{i}".encode())
        info = log.wait_durable(log.durability_ticket())
        assert info["led"]
    took = time.perf_counter() - t0
    # a solo committer must never serve the window (held_drains == 0)
    # nor pay it (5 drains through a 1 s window would take > 5 s)
    assert log.held_drains == 0
    assert log.fsyncs == 5
    assert took < 2.0
    log.close()


def test_concurrent_committers_share_fsyncs(tmp_path, backend):
    log = DurableLog(str(tmp_path / "mt"), backend=backend,
                     group=grp(group_us=2000, group_records=512))
    n_threads, per = 8, 30
    errs = []

    def committer(i):
        try:
            for j in range(per):
                log.append(f"t{i}-{j}".encode())
                log.wait_durable(log.durability_ticket())
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    ts = [threading.Thread(target=committer, args=(i,))
          for i in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs
    qs = log.queue_stats()
    assert qs["synced_end"] == qs["end"]
    assert qs["drained_records"] == n_threads * per
    # group commit: strictly fewer fsyncs than commits (legacy = one
    # per commit); the exact ratio is timing-dependent, the direction
    # is not
    assert log.fsyncs < n_threads * per
    log.close()
    # every record survived, in a consistent order
    rec = DurableLog(str(tmp_path / "mt"), backend=backend)
    got = [b for _o, b in rec.scan()]
    assert sorted(got) == sorted(
        f"t{i}-{j}".encode() for i in range(n_threads) for j in range(per))
    # per-thread order preserved (appends are ordered per committer)
    for i in range(n_threads):
        mine = [b for b in got if b.startswith(f"t{i}-".encode())]
        assert mine == [f"t{i}-{j}".encode() for j in range(per)]
    rec.close()


def test_follower_ticket_covered_by_leader(tmp_path, backend):
    """A waiter whose ticket the in-flight drain covers returns
    without leading (led=False)."""
    log = DurableLog(str(tmp_path / "fw"), backend=backend,
                     group=grp(group_us=50_000))
    log.append(b"a")
    t_a = log.durability_ticket()
    results = {}
    barrier = threading.Barrier(2)

    def leader():
        barrier.wait()
        results["lead"] = log.wait_durable(t_a)

    def follower():
        barrier.wait()
        time.sleep(0.005)  # let the other thread take the lead
        results["follow"] = log.wait_durable(t_a)

    ts = [threading.Thread(target=leader),
          threading.Thread(target=follower)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert {results["lead"]["led"], results["follow"]["led"]} == \
        {True, False}
    log.close()


def test_staged_budget_writes_through(tmp_path, backend):
    log = DurableLog(str(tmp_path / "bp"), backend=backend,
                     group=grp(group_records=8))
    for i in range(20):
        log.append(f"x{i}".encode())
    # the budget bounded the staged queue (multiple write-throughs)
    assert len(log._staged) < 8
    assert log.queue_stats()["written_end"] > 0
    # nothing synced yet — write-through is buffered, not durable
    assert log.queue_stats()["synced_end"] == 0
    log.close()


def test_staged_byte_budget_writes_through(tmp_path, backend):
    """Large payloads must not pin unbounded heap: the BYTE budget
    writes staged records through well before the record cap."""
    log = DurableLog(str(tmp_path / "bb"), backend=backend,
                     group=grp(group_records=10_000,
                               group_bytes=64 * 1024))
    big = b"x" * 8192
    for _ in range(20):
        log.append(big)
    assert log._staged_bytes < 64 * 1024
    assert log.queue_stats()["written_end"] > 0
    log.close()


def test_reads_drain_staged(tmp_path, backend):
    log = DurableLog(str(tmp_path / "rd"), backend=backend, group=grp())
    offs = [log.append(f"s{i}".encode()) for i in range(5)]
    assert log.read(offs[3]) == b"s3"  # staged records readable
    assert [b for _o, b in log.scan()] == [f"s{i}".encode()
                                           for i in range(5)]
    log.close()


def test_sync_off_the_handle_lock(tmp_path):
    """A slow fsync must not stall concurrent reads: the refcounted
    close guard runs the fsync outside the handle lock (python backend
    — the sleep is injected at the backend sync)."""
    log = DurableLog(str(tmp_path / "slow"), backend="python",
                     group=grp())
    off = log.append(b"payload")
    log.flush()
    orig = log._py.sync
    entered = threading.Event()

    def slow_sync():
        entered.set()
        time.sleep(0.5)
        orig()

    log._py.sync = slow_sync
    t = threading.Thread(target=log.sync)
    t.start()
    assert entered.wait(2.0)
    t0 = time.perf_counter()
    assert log.read(off) == b"payload"
    read_took = time.perf_counter() - t0
    t.join()
    assert read_took < 0.25, \
        f"read stalled {read_took:.3f}s behind the fsync"
    log.close()


def test_close_waits_for_inflight_fsync(tmp_path):
    log = DurableLog(str(tmp_path / "cw"), backend="python",
                     group=grp())
    log.append(b"x")
    log.flush()
    orig = log._py.sync
    entered = threading.Event()

    def slow_sync():
        entered.set()
        time.sleep(0.3)
        orig()

    log._py.sync = slow_sync
    t = threading.Thread(target=log.sync)
    t.start()
    assert entered.wait(2.0)
    t0 = time.perf_counter()
    log.close()  # must block until the fsync drains, then free
    assert time.perf_counter() - t0 > 0.1
    t.join()


# -------------------------------------------------------- partition level


def test_partition_commit_ticket_and_wait(tmp_path, backend):
    plog = PartitionLog(str(tmp_path / "pc"), partition=0,
                        sync_on_commit=True, backend=backend,
                        group=grp())
    plog.append_update("dc1", "t1", "k", "counter_pn", 1)
    plog.append_commit("dc1", "t1", 5, VC())
    ticket = plog.commit_ticket()
    assert ticket is not None and ticket > 0
    plog.wait_durable(ticket, txid="t1")
    assert plog.log.queue_stats()["synced_end"] >= ticket
    # sync off: no ticket
    plog.sync_on_commit = False
    plog.append_commit("dc1", "t2", 6, VC())
    assert plog.commit_ticket() is None
    plog.close()


def test_partition_legacy_sync_inline(tmp_path, backend):
    plog = PartitionLog(str(tmp_path / "pl"), partition=0,
                        sync_on_commit=True, backend=backend,
                        group=grp(enabled=False))
    before = plog.log.fsyncs
    plog.append_commit("dc1", "t1", 5, VC())
    assert plog.log.fsyncs == before + 1  # inline, per record
    assert plog.commit_ticket() is None   # nothing to wait on
    plog.close()


def test_remote_group_returns_ticket(tmp_path, backend):
    from antidote_tpu.oplog.records import LogRecord, OpId

    plog = PartitionLog(str(tmp_path / "rg"), partition=0,
                        sync_on_commit=True, backend=backend,
                        group=grp())
    recs = [
        LogRecord(OpId("dcR", 1), "rt", ("update", "k", "counter_pn", 2)),
        LogRecord(OpId("dcR", 2), "rt",
                  ("commit", ("dcR", 9), VC.from_list([("dcR", 8)]))),
    ]
    ticket = plog.append_remote_group(recs)
    assert ticket is not None
    plog.wait_durable(ticket)
    assert plog.log.queue_stats()["synced_end"] >= ticket
    plog.close()


def test_log_stats_shape(tmp_path):
    plog = PartitionLog(str(tmp_path / "ls"), partition=0, group=grp())
    plog.append_update("dc1", "t", "k", "counter_pn", 1)
    s = plog.log_stats()
    assert s["enabled"] and s["group"]
    assert s["staged_records"] == 1 and s["staged_bytes"] > 0
    assert s["oldest_staged_age_us"] >= 0
    off = PartitionLog(str(plog.path) + ".off", partition=0,
                       enabled=False)
    assert off.log_stats() == {"enabled": False}
    off.close()
    plog.close()


def test_log_counters_populate(tmp_path):
    reg = stats.registry
    f0 = reg.log_fsyncs.value()
    r0 = reg.log_group_records.value()
    log = DurableLog(str(tmp_path / "cnt"), backend="python",
                     group=grp())
    for i in range(4):
        log.append(f"c{i}".encode())
    log.wait_durable(log.durability_ticket())
    assert reg.log_fsyncs.value() == f0 + 1
    assert reg.log_group_records.value() == r0 + 4
    assert reg.log_records_per_fsync.value() > 0
    assert reg.log_group_size.count > 0
    log.close()


def test_failed_batch_write_keeps_staged_and_offsets(tmp_path, backend):
    """A failing backend write (disk full) must NOT drop the staged
    records: they stay staged, assigned offsets stay consistent with
    the file, and a later retry writes them where promised."""
    log = DurableLog(str(tmp_path / "ff"), backend=backend, group=grp())
    offs = [log.append(f"k{i}".encode()) for i in range(3)]
    orig = log._append_batch_backend_locked
    calls = {"n": 0}

    def failing(payloads):
        calls["n"] += 1
        if calls["n"] == 1:
            raise OSError("disk full")
        return orig(payloads)

    log._append_batch_backend_locked = failing
    with pytest.raises(OSError):
        log.flush()
    # nothing lost, accounting intact
    assert len(log._staged) == 3
    assert log.queue_stats()["written_end"] == 0
    assert log.end_offset() == log._logical_end
    # retry succeeds and lands every record at its assigned offset
    log.flush()
    for off, want in zip(offs, [b"k0", b"k1", b"k2"]):
        assert log.read(off) == want
    log.close()


def test_wait_durable_times_out_on_uncoverable_ticket(tmp_path):
    """A ticket the drains can never cover (wedged accounting) must
    raise TimeoutError instead of re-electing a leader forever in a
    hot fsync loop."""
    log = DurableLog(str(tmp_path / "to"), backend="python",
                     group=grp())
    log.append(b"x")
    bogus = log.durability_ticket() + 10_000
    t0 = time.perf_counter()
    with pytest.raises(TimeoutError):
        log.wait_durable(bogus, timeout=0.3)
    assert time.perf_counter() - t0 < 5.0
    log.close()


def test_sync_wait_span_joins_txn_tree(tmp_path):
    """The per-committer log_sync_wait is a WAIT span that carries the
    txid, so a sampled transaction's tree shows what its commit ack
    paid for durability and no work span above it counts the sleep as
    its own; the leader's drain and its fsync nest under it, the fsync
    a wait span around the backend's call alone."""
    from antidote_tpu.obs.spans import tracer

    old_rate = tracer.sample_rate
    tracer.sample_rate = 1.0
    try:
        plog = PartitionLog(str(tmp_path / "tr"), partition=0,
                            sync_on_commit=True, group=grp())
        txid = ("dc1", 4242)
        plog.append_update("dc1", txid, "k", "counter_pn", 1)
        plog.append_commit("dc1", txid, 5, VC())
        plog.wait_durable(plog.commit_ticket(), txid=txid)
        (wait,) = tracer.spans(txid=txid, name="log_sync_wait")
        assert (wait.cat, wait.kind) == ("oplog", "wait")
        assert wait.args["led"] is True and wait.args["records"] == 2
        assert wait.args["partition"] == 0
        (drain,) = tracer.spans(txid=txid, name="log_group_drain")
        (fsync,) = tracer.spans(txid=txid, name="log_fsync")
        assert drain.kind == "work" and drain.parent_id == wait.span_id
        assert fsync.kind == "wait" and fsync.parent_id == drain.span_id
        plog.close()
    finally:
        tracer.sample_rate = old_rate


class _SlowSync:
    """A pinned backend whose sync sleeps first: the disk of a slow
    machine, inside ``_backend_sync``'s own call."""

    def __init__(self, io, seconds):
        self.io, self.seconds = io, seconds

    def sync(self):
        time.sleep(self.seconds)
        if isinstance(self.io, tuple):
            self.io[0].oplog_sync(self.io[1])
        else:
            self.io.sync()


def _committed_capture(tmp_path, monkeypatch, sync_log, slow_s=0.0):
    """Six static updates through the API — three of one key, three of
    eight keys over both partitions — inside a capture; the capture's
    summary."""
    import contextlib

    from antidote_tpu.api import AntidoteTPU
    from antidote_tpu.obs.spans import summarize, tracer

    if slow_s:
        real = DurableLog._backend_sync
        monkeypatch.setattr(
            DurableLog, "_backend_sync",
            lambda self, io: real(self, _SlowSync(io, slow_s)))
    db = AntidoteTPU(config=Config(n_partitions=2, sync_log=sync_log,
                                   device_store=False),
                     data_dir=str(tmp_path / "data"))
    keys = [(i, "counter_pn", "b") for i in range(8)]  # i % 2
    tracer.capture_begin(lambda name: contextlib.nullcontext())
    try:
        clock = None
        for _ in range(3):
            clock = db.update_objects_static(
                clock, [(keys[0], "increment", 1)])
            clock = db.update_objects_static(
                clock, [(k, "increment", 1) for k in keys])
    finally:
        raw = tracer.capture_end()
    assert db.read_objects_static(clock, keys)[0] == [6] + [3] * 7
    db.close()
    return summarize(**raw)


def test_a_slow_fsync_is_nobodys_work(tmp_path, monkeypatch):
    """Every fsync slowed to 20 ms inside a capture: the sleeps are
    wait spans, so the capture's ``host_busy_s`` leaves them out, and
    so does the self time of the coordinator's commit spans above
    them (``frontend_self_ms_per_txn`` and ``host_busy_pct`` read
    those)."""
    cap = _committed_capture(tmp_path, monkeypatch, True, slow_s=0.02)
    rows = cap["spans"]
    fsync, wait = rows["log_fsync"], rows["log_sync_wait"]
    assert fsync["kind"] == wait["kind"] == "wait"
    # three single-partition commits and three over two partitions
    assert wait["count"] == 3 + 3 * 2 and fsync["count"] >= wait["count"]
    slept = fsync["total_s"]
    assert slept >= 0.02 * fsync["count"]
    assert wait["total_s"] >= slept - 1e-4
    assert rows["log_group_drain"]["self_s"] < 0.02
    assert (rows["single_commit"]["count"],
            rows["2pc_commit"]["count"]) == (3, 3)
    for name, waits in (("single_commit", 3), ("2pc_commit", 6)):
        row = rows[name]
        assert row["total_s"] >= 0.02 * waits
        assert row["self_s"] <= row["total_s"] - 0.02 * waits, name
    # one thread committed: what it slept on the disk it was not busy
    assert cap["host_busy_s"] <= cap["length_s"] - slept + 1e-4


def test_without_sync_log_a_commit_records_neither_wait(tmp_path,
                                                        monkeypatch):
    cap = _committed_capture(tmp_path, monkeypatch, False)
    assert cap["spans"]["single_commit"]["count"] == 3
    assert cap["spans"]["2pc_commit"]["count"] == 3
    assert "log_sync_wait" not in cap["spans"]
    assert "log_fsync" not in cap["spans"]
    assert "log_group_drain" not in cap["spans"]


def test_recovery_identical_across_group_modes(tmp_path, backend):
    """PartitionLog recovery (op counters, max VC, key index) from a
    group-written file equals recovery from a legacy-written one."""
    def drive(path, group):
        plog = PartitionLog(path, partition=0, sync_on_commit=True,
                            backend=backend, group=group)
        for i in range(10):
            plog.append_update("dc1", f"t{i}", f"k{i % 3}",
                               "counter_pn", i)
            plog.append_commit("dc1", f"t{i}", 100 + i,
                               VC.from_list([("dc1", 90 + i)]))
            plog.wait_durable(plog.commit_ticket(), txid=f"t{i}")
        plog.close()

    gp, lp = str(tmp_path / "g"), str(tmp_path / "l")
    drive(gp, grp())
    drive(lp, grp(enabled=False))
    assert (tmp_path / "g").read_bytes() == (tmp_path / "l").read_bytes()
    rg = PartitionLog(gp, partition=0, backend=backend)
    rl = PartitionLog(lp, partition=0, backend=backend)
    assert rg.op_counters == rl.op_counters
    assert rg.max_commit_vc == rl.max_commit_vc
    assert rg.key_commits == rl.key_commits
    assert [(i, p.key, p.effect) for i, p in rg.committed_payloads()] \
        == [(i, p.key, p.effect) for i, p in rl.committed_payloads()]
    rg.close()
    rl.close()
