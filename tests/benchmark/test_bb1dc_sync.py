"""The durable deployment ``bb1dc-sync`` and its cell (PR 33): what
``load_cell`` gives it and leaves every other cell, the two readers of
the durable log's metrics, a whole traced run rehearsed on the CPU in
which commits wait for fsyncs, and durability itself held against a
plain reference: what a copy of the data directory taken under load
recovers to."""

import json
import os
import shutil
import threading

import pytest

from test_rehearsal import on_the_cpu  # noqa: F401 — the fixture

from antidote_tpu.obs import prof
from benchmark import harness, run
from benchmark.harness import WindowView

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "bb1dc-sync.update90-uniform"
NEW = {"log_sync_wait_ms_per_txn", "log_records_per_fsync"}
# what the accepted cells reported before this cell came (ledger, PR 32);
# held as sets: a later PR may add to a cell, in any place of the list
HAD = {
    "bb1dc.read90-uniform": {
        "read_cache_hit_pct", "read_dispatches_per_read",
        "compiles_in_window", "ops_per_flush", "kernels_roofline",
        "device_idle_pct", "frontend_self_ms_per_txn",
        "serve_queue_wait_p95_ms", "manager_wait_ms_per_txn",
        "device_host_ms_per_dispatch", "host_busy_pct"},
    "bb1dc.update90-uniform": {
        "compiles_in_window", "ops_per_flush", "kernels_roofline",
        "device_idle_pct", "frontend_self_ms_per_txn",
        "manager_wait_ms_per_txn", "host_busy_pct"},
}
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
OTHERS = [w["name"] for w in BENCH["workloads"] if w["name"] != CELL]


def names(cell) -> set:
    return {m["name"] for m in cell.per_layer}


# ------------------------------------------------- the cell, as declared


def test_the_cell_is_bb1dc_with_sync_log_and_nothing_else_changed():
    cell = harness.load_cell(ROOT, CELL)
    assert cell.config["config"] == {"sync_log": True}
    assert cell.chips == 1
    assert [m["name"] for m in cell.end_to_end] == [
        "txn_per_s", "update_p95_ms", "setup_s"]
    sibling = harness.load_cell(ROOT, "bb1dc.update90-uniform")
    assert cell.mix == sibling.mix and cell.mix_file == sibling.mix_file
    assert names(cell) == names(sibling) | NEW
    ours, theirs = cell.config, sibling.config
    for key in ("partitions", "keys_per_partition", "types", "published",
                "reduced", "why_reduced"):
        assert ours[key] == theirs[key], key
    for key in ("isolation", "consistency", "certification", "visibility"):
        assert ours["guarantees"][key] == theirs["guarantees"][key], key
    durability = ours["guarantees"]["durability"]
    assert "fsynced" in durability and "every partition" in durability
    assert ours["assumed"][:len(theirs["assumed"])] == theirs["assumed"]
    assert any("log_group_us 300" in a for a in ours["assumed"])
    # the node's configuration: the one field, the defaults for the rest
    from antidote_tpu.config import Config

    dep = harness.Deployment.__new__(harness.Deployment)
    dep.cell, dep.ks, dep.workdir = cell, cell.keyspace, "/nowhere"
    node_cfg = dep._node_config()
    want = Config(n_partitions=4, sync_log=True,
                  flight_recorder_dir="/nowhere/obs")
    assert node_cfg == want
    assert (node_cfg.log_group, node_cfg.log_group_us,
            node_cfg.publish_after_durable) == (True, 300, False)


@pytest.mark.parametrize("workload", OTHERS)
def test_every_other_cell_reports_what_it_did(workload):
    cell = harness.load_cell(ROOT, workload)
    assert not NEW & names(cell)
    if workload in HAD:
        assert HAD[workload] <= names(cell)
        assert cell.config["config"] == {}


def test_the_two_metrics_are_the_durable_logs_and_this_cells_alone():
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    assert declared["log_sync_wait_ms_per_txn"] == {
        "name": "log_sync_wait_ms_per_txn", "unit": "ms/txn",
        "better": "lower", "source": "program_span",
        "layer": "durable log", "moves": "update_p95_ms",
        "workloads": [CELL]}
    assert declared["log_records_per_fsync"] == {
        "name": "log_records_per_fsync", "unit": "ops",
        "better": "higher", "source": "program_counter",
        "layer": "durable log", "moves": "txn_per_s",
        "workloads": [CELL]}
    assert [w["name"] for w in BENCH["workloads"]
            if w["config"] == "bb1dc-sync"] == [CELL]


# ------------------------------------------------ the readers' arithmetic


def reader(name):
    return harness._load_reader(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"))


def view(trace_reading=None, **counters):
    return WindowView(counters=counters, answered={}, update_ops=0,
                      trace=trace_reading)


SLICE = {"busy_s": 1.0, "window_s": 3.0}
SUMMARY = {
    "length_s": 3.0, "requests_answered": 500, "host_busy_s": 2.0,
    "spans": {"log_sync_wait": {"cat": "oplog", "kind": "wait",
                                "count": 1900, "total_s": 9.5,
                                "self_s": 9.0, "p95_s": 0.011},
              "log_fsync": {"cat": "oplog", "kind": "wait", "count": 700,
                            "total_s": 2.1, "self_s": 2.1,
                            "p95_s": 0.004}}}


def test_the_sync_wait_readers_arithmetic(monkeypatch):
    monkeypatch.setattr(prof, "last_capture", lambda: SUMMARY)
    read = reader("log_sync_wait_ms_per_txn")
    assert read(view(SLICE)) == pytest.approx(1000.0 * 9.5 / 500)
    assert read(view(None)) is None


@pytest.mark.parametrize("program", [
    "no capture yet", "obs.prof without last_capture", "sync_log false",
    "the parent's instant", "no request answered"])
def test_the_sync_wait_reader_with_nothing_to_read(monkeypatch, program):
    if program == "no capture yet":
        monkeypatch.setattr(prof, "last_capture", lambda: None)
    elif program == "obs.prof without last_capture":
        monkeypatch.delattr(prof, "last_capture")
    elif program == "sync_log false":
        monkeypatch.setattr(prof, "last_capture",
                            lambda: dict(SUMMARY, spans={}))
    elif program == "the parent's instant":
        instant = {"cat": "oplog", "kind": "work", "count": 1900,
                   "total_s": 0.0, "self_s": 0.0, "p95_s": 0.0}
        monkeypatch.setattr(prof, "last_capture", lambda: dict(
            SUMMARY, spans={"log_sync_wait": instant}))
    else:
        monkeypatch.setattr(prof, "last_capture", lambda: dict(
            SUMMARY, requests_answered=0))
    got = reader("log_sync_wait_ms_per_txn")(view(SLICE))
    assert got == 0.0 and isinstance(got, float)


def test_the_records_per_fsync_readers_arithmetic():
    read = reader("log_records_per_fsync")
    assert read(view(log_fsyncs=40, log_group_records=1000)) == 25.0
    # no trace needed: it reads the window's counters
    assert read(view(SLICE, log_fsyncs=3, log_group_records=3)) == 1.0
    # never a 0 in place of a ratio: the harness leaves the metric out
    assert read(view(log_fsyncs=0, log_group_records=0)) is None


# ------------------------------------------- a whole traced run, rehearsed


def test_a_rehearsed_traced_run_waits_for_fsyncs(tiny_root, on_the_cpu,
                                                 capsys):
    """test_rehearsal.py's hooks; the capture opens through ``obs.prof``,
    so the wait spans are there to read."""
    rc = run.main(["--workload", CELL, "--seed", str(2**31 + 33),
                   "--seconds", "4", "--trace", "1"], root=tiny_root)
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    line = json.loads(lines[-1])
    detail = json.loads(lines[-2][len("DETAIL "):])
    assert line["correct"] is True and line["failed"] == 0
    counters = detail["counters"]
    assert counters["log_fsyncs"] > 0
    assert counters["log_group_records"] >= counters["log_fsyncs"]
    cell = harness.load_cell(tiny_root, CELL)
    assert set(line["metrics"]) == {m["name"] for m in cell.per_layer}
    for name in NEW:
        assert line["metrics"][name]["value"] > 0, line["metrics"]
    assert line["metrics"]["log_records_per_fsync"]["value"] >= 1.0
    cap = prof.last_capture()
    assert cap["dropped"] == 0
    for name in ("log_sync_wait", "log_fsync"):
        assert cap["spans"][name]["kind"] == "wait", name
        assert cap["spans"][name]["count"] > 0, name
    # every wait stands under a served request: one a partition written
    updates = cap["requests"]["ApbStaticUpdateObjects"]["count"]
    assert cap["spans"]["log_sync_wait"]["count"] >= updates > 0


# ------------------------------------ durability, against a plain reference

THREADS, TXNS, ELEMS = 8, 25, ("a", "b", "c", "d", "e", "f")


def keys_of(thread: int) -> list:
    """Four keys of the thread's own, two a partition (key % 2): a
    counter and a set on each."""
    base = 4 * thread
    return [(base, "counter_pn", "b"), (base + 1, "counter_pn", "b"),
            (base + 2, "set_aw", "b"), (base + 3, "set_aw", "b")]


def updates_of(thread: int, n: int) -> list:
    """The thread's ``n``-th transaction (from 1)."""
    c0, c1, s0, s1 = keys_of(thread)
    elem = ELEMS[n % len(ELEMS)]
    return [(c0, "increment", n), (c1, "decrement", 1),
            (s0, "add", elem),
            (s1, "remove" if n % 3 == 0 else "add",
             ELEMS[(n - 1) % len(ELEMS)] if n % 3 == 0 else elem)]


def plain_after(thread: int, count: int) -> list:
    """The four keys after the thread's first ``count`` transactions:
    plain ints and frozensets, no code of the program."""
    c0 = c1 = 0
    s0, s1 = set(), set()
    for n in range(1, count + 1):
        c0, c1 = c0 + n, c1 - 1
        s0.add(ELEMS[n % len(ELEMS)])
        if n % 3 == 0:
            s1.discard(ELEMS[(n - 1) % len(ELEMS)])
        else:
            s1.add(ELEMS[n % len(ELEMS)])
    return [c0, c1, frozenset(s0), frozenset(s1)]


def plain(values: list) -> list:
    return [v if isinstance(v, int) else frozenset(v) for v in values]


def watch_real_syncs(monkeypatch, logs) -> dict:
    """The calls that reach the disk, wrapped BELOW ``DurableLog``:
    ``os.fsync`` (the Python backend's) and the native library's
    ``oplog_sync``.  Gives {path: the highest logical end that a call
    which has RETURNED covered}: what was in the file (Python) or in the
    backend (native, whose sync flushes first) when the call began."""
    reached = {dl.path: 0 for dl in logs}
    guard = threading.Lock()
    by_fd = {dl._py.f.fileno(): dl for dl in logs if dl._py is not None}
    by_handle = {dl._native[1].value: dl for dl in logs if dl._native}

    def note(dl, end):
        if dl is not None:
            with guard:
                reached[dl.path] = max(reached[dl.path], end + dl._delta)

    real_fsync = os.fsync

    def fsync(fd):
        end = os.fstat(fd).st_size
        real_fsync(fd)
        note(by_fd.get(fd), end)

    monkeypatch.setattr(os, "fsync", fsync)
    for lib in {id(dl._native[0]): dl._native[0]
                for dl in logs if dl._native}.values():
        def oplog_sync(handle, lib=lib, real=lib.oplog_sync):
            end = lib.oplog_end_offset(handle)
            real(handle)
            note(by_handle.get(handle.value), end)

        monkeypatch.setattr(lib, "oplog_sync", oplog_sync)
    return reached


def killed_under_load(tmp_path, monkeypatch, sync_log: bool,
                      backend: str = "auto") -> dict:
    """Eight threads commit; mid-run no thread may start another
    transaction, the acknowledged counts are taken, and then the data
    directory is copied with the node still open — what a killed
    PROCESS leaves: bytes staged or buffered in the process are lost,
    bytes written to the file are not, fsynced or not (the copy reads
    through the page cache, so it cannot tell a power loss).  That the
    bytes were fsynced is held at each acknowledgement instead:
    ``reached`` says whether a real sync call that covered the ticket
    had returned, ``covered`` whether the log's own watermark said so.
    What a second node recovers from the copy, beside those."""
    from antidote_tpu.api import AntidoteTPU
    from antidote_tpu.config import Config
    from antidote_tpu.oplog.partition import PartitionLog

    cfg = Config(n_partitions=2, sync_log=sync_log,
                 extra={"oplog_backend": backend})
    live_dir, copy_dir = str(tmp_path / "live"), str(tmp_path / "copy")
    db = AntidoteTPU(config=cfg, data_dir=live_dir)
    logs = [pm.log.log for pm in db.node.partitions]
    synced = watch_real_syncs(monkeypatch, logs)
    covered, reached = [], []
    real_wait = PartitionLog.wait_durable

    def checked_wait(self, ticket, txid=None):
        real_wait(self, ticket, txid=txid)
        if ticket is not None:
            covered.append(
                self.log.queue_stats()["synced_end"] >= ticket)
            reached.append(synced[self.log.path] >= ticket)

    monkeypatch.setattr(PartitionLog, "wait_durable", checked_wait)
    acked, errors = [0] * THREADS, []
    may_start = threading.Event()
    may_start.set()
    half_way = threading.Semaphore(0)

    def committer(i):
        clock = None
        try:
            for n in range(1, TXNS + 1):
                may_start.wait()
                clock = db.update_objects_static(clock, updates_of(i, n))
                acked[i] = n
                if n == TXNS // 2:
                    half_way.release()
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errors.append(e)
            half_way.release()

    threads = [threading.Thread(target=committer, args=(i,))
               for i in range(THREADS)]
    for t in threads:
        t.start()
    for _ in range(THREADS // 2):   # half the threads are half way
        half_way.acquire()
    may_start.clear()
    acknowledged = list(acked)
    staged = sum(dl.queue_stats()["staged_records"] for dl in logs)
    shutil.copytree(live_dir, copy_dir)
    may_start.set()
    for t in threads:
        t.join(120)
        assert not t.is_alive()
    assert not errors, errors
    assert acked == [TXNS] * THREADS
    live = [db.read_objects_static(None, keys_of(i))[0]
            for i in range(THREADS)]
    db.close()
    monkeypatch.undo()    # the second node's files are not watched
    db2 = AntidoteTPU(config=cfg, data_dir=copy_dir)
    recovered = [db2.read_objects_static(None, keys_of(i))[0]
                 for i in range(THREADS)]
    db2.close()
    return {"acknowledged": acknowledged, "staged_at_copy": staged,
            "covered": covered, "reached": reached,
            "native": [bool(dl._native) for dl in logs],
            "live": [plain(v) for v in live],
            "recovered": [plain(v) for v in recovered]}


def nothing_acknowledged_is_missing(got: dict) -> None:
    assert sum(got["acknowledged"]) >= THREADS // 2 * (TXNS // 2)
    for i in range(THREADS):
        assert got["live"][i] == plain_after(i, TXNS)
        n = got["acknowledged"][i]
        # a transaction in flight at the copy may be there, on one of
        # its two partitions or on both: each key holds the
        # acknowledged prefix or one transaction more, never less
        for key, value in enumerate(got["recovered"][i]):
            assert value in (plain_after(i, n)[key],
                             plain_after(i, min(n + 1, TXNS))[key]), \
                (i, key, n, value)


@pytest.mark.parametrize("backend", ["auto", "python"])
def test_an_acknowledged_commit_survives_a_kill(tmp_path, monkeypatch,
                                                backend):
    got = killed_under_load(tmp_path, monkeypatch, True, backend)
    if backend == "python":
        assert got["native"] == [False, False]
    nothing_acknowledged_is_missing(got)
    # at every acknowledgement (two partitions a transaction) the
    # synced watermark covered the ticket, and a call that reached the
    # disk and covered it had returned: os.fsync itself on the Python
    # backend, the library's flush-and-fsync on the native one
    assert len(got["covered"]) == 2 * THREADS * TXNS
    assert all(got["covered"])
    assert len(got["reached"]) == 2 * THREADS * TXNS
    assert all(got["reached"])


def test_a_sync_that_flushes_and_never_fsyncs_is_seen(tmp_path,
                                                      monkeypatch):
    """The control of ``reached``: a backend whose sync keeps the flush
    and drops the fsync loses nothing to a killed process, and its
    watermark says all is well; only the watch on the real call tells."""
    from antidote_tpu.oplog import log as oplog

    monkeypatch.setattr(oplog._PyLog, "sync", oplog._PyLog.flush)
    got = killed_under_load(tmp_path, monkeypatch, True, "python")
    nothing_acknowledged_is_missing(got)
    assert all(got["covered"])
    assert len(got["reached"]) == 2 * THREADS * TXNS
    assert not any(got["reached"])


def test_without_sync_log_the_same_kill_loses_acknowledged_commits(
        tmp_path, monkeypatch):
    """The control: the comparison above can fail.  ``sync_log`` false
    acknowledges a commit once it is staged; the copy holds none of
    the staged bytes."""
    got = killed_under_load(tmp_path, monkeypatch, sync_log=False)
    assert got["covered"] == [] == got["reached"]   # no ticket, no wait
    assert got["staged_at_copy"] > 0
    behind = [i for i in range(THREADS)
              if got["acknowledged"][i] > 0
              and got["recovered"][i] != plain_after(
                  i, got["acknowledged"][i])
              and got["recovered"][i] != plain_after(
                  i, min(got["acknowledged"][i] + 1, TXNS))]
    assert behind, got
    for i in range(THREADS):
        assert got["live"][i] == plain_after(i, TXNS)
