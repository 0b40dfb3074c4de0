"""The two-DC deployment ``bb2dc`` and its cell: rehearsed on the CPU at
a tiny keyspace with the harness's look for a chip left out.  Writers
at dc1, probers that commit at dc1 and read the same keys at dc2 at the
returned clock; every prober value at dc2 is compared, the read-back
runs at both DCs, the control (each dc2 read answered at its clock less
one) comes out wrong, and so does a run with the replication broken
underneath.  A configuration without ``dcs`` builds what it built
before: one ``AntidoteTPU``, the same counters."""

import json
import os
import time

import pytest

from bench_tiny import tiny_tree
from benchmark import harness, reference, run, trace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
RECORDED = os.path.join(ROOT, "benchmark", "data", "v5e_micro.xplane.pb")
CELL = "bb2dc.update90-probed"
REMOTE = ("remote_reads_compared", "remote_reads_wrong",
          "remote_snapshots_behind_commit", "remote_acks_read_back",
          "remote_acks_unreadable")
NEW = {"depgate_wait_ms_per_remote_txn", "remote_apply_ms_per_txn",
       "ship_txns_per_frame", "vis_lag_p50_ms"}
#: the per-layer metrics every cell reports that reports what they move
FOR_ALL = {"compiles_in_window", "ops_per_flush", "kernels_roofline",
           "device_idle_pct", "frontend_self_ms_per_txn",
           "manager_wait_ms_per_txn", "host_busy_pct"}
#: what a one-DC deployment's counters held before the second DC came
ONE_DC_COUNTERS = {
    "read_dispatches", "read_cache_hits", "read_cache_misses",
    "read_serve_groups", "ingest_dispatches", "log_fsyncs",
    "log_group_records", "ingest_flushes", "gc_folds", "kernel_calls",
    "kernel_compile_misses", "jax_programs_compiled"}
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def cpu_hooks(mp):
    """test_rehearsal.py's hooks: no look for a chip, short warm-up
    phases, a value cache small enough that reads reach the planes,
    and the recorded v5e trace in place of a CPU capture."""
    from antidote_tpu.txn.manager import PartitionManager

    mp.setattr(run, "find_chip", lambda chips: None)
    mp.setattr(harness, "WARM_MIN_PHASES", 1)
    mp.setattr(harness, "WARM_QUIET_PHASES", 1)
    mp.setattr(harness, "WARM_PHASE_S", 1.0)
    init = PartitionManager.__init__

    def small_cache(self, *a, **kw):
        init(self, *a, **kw)
        self._val_cache_cap = 64

    mp.setattr(PartitionManager, "__init__", small_cache)
    mp.setattr(trace, "xplane_of", lambda log_dir: RECORDED)
    mp.setitem(trace.PEAKS, "cpu", trace.PEAKS["TPU v5 lite"])


def one_window(root, seed, seconds=2.0):
    """Set-up and one window of the cell; (cell, reading, reduced,
    history)."""
    cell = harness.load_cell(root, CELL)
    dep = harness.Deployment(cell, seed)
    try:
        dep.open()
        reading = dep.measure(seed, seconds, False, time.monotonic())
    finally:
        dep.close()
    reduced = harness.reduce_reading(cell, reading, dep.history)
    return cell, reading, reduced, dep.history


def numbers(reduced):
    return {n: v for n, v, _c, _l in reduced["numbers"]}


@pytest.fixture(scope="module")
def window(tmp_path_factory):
    with pytest.MonkeyPatch.context() as mp:
        cpu_hooks(mp)
        root = tiny_tree(str(tmp_path_factory.mktemp("bb2dc") / "tree"))
        yield one_window(root, 2**31 + 4101)


# ------------------------------------------------- the cell, as declared


def test_the_cell_is_bb1dc_in_two_dcs_with_probers():
    cell = harness.load_cell(ROOT, CELL)
    sibling = harness.load_cell(ROOT, "bb1dc.update90-uniform")
    assert cell.dcs == 2 and sibling.dcs == 1 and cell.chips == 1
    assert cell.keyspace == sibling.keyspace
    assert cell.config["config"] == sibling.config["config"] == {}
    assert cell.config["types"] == sibling.config["types"]
    for name, said in sibling.config["guarantees"].items():
        assert cell.config["guarantees"][name] == said, name
    assert "replication" in cell.config["guarantees"]
    # the source's three DCs cut to two, listed beside the partitions
    assert cell.config["reduced"] == sibling.config["reduced"] + ["dcs"]
    assert cell.config["published"] == {"partitions": 16, "dcs": 3}
    entry = next(c for c in BENCH["configs"] if c["name"] == "bb2dc")
    assert entry["reduced"] == cell.config["reduced"]
    # the writers are update90-uniform's, the probers come beside them
    assert (cell.mix.clients, cell.mix.probers) == (4, 4)
    for key in ("operations", "num_reads", "num_updates", "key_generator",
                "retry_for_s", "retry_pause_ms"):
        assert getattr(cell.mix, key) == getattr(sibling.mix, key), key
    assert [m["name"] for m in cell.end_to_end] == [
        "txn_per_s", "update_p95_ms", "setup_s", "vis_lag_p95_ms"]
    assert {m["name"] for m in cell.per_layer} == NEW | FOR_ALL
    assert all(m["moves"] == "vis_lag_p95_ms" for m in cell.per_layer
               if m["name"] in NEW)


@pytest.mark.parametrize("workload", [
    w["name"] for w in BENCH["workloads"] if w["name"] != CELL])
def test_no_other_cell_reports_the_inter_dc_metrics(workload):
    cell = harness.load_cell(ROOT, workload)
    assert cell.dcs == 1 and cell.mix.probers == 0
    assert not NEW & {m["name"] for m in cell.per_layer}
    assert "vis_lag_p95_ms" not in {m["name"] for m in cell.end_to_end}


def test_probers_without_a_second_dc_are_refused(tiny_root):
    path = os.path.join(tiny_root, "benchmark", "configs", "bb2dc.json")
    with open(path) as f:
        cfg = json.load(f)
    del cfg["dcs"]
    with open(path, "w") as f:
        json.dump(cfg, f)
    with pytest.raises(harness.BenchError, match="probers"):
        harness.load_cell(tiny_root, CELL)


# ------------------------------------------------- one rehearsed window


def test_the_cell_runs_end_to_end_and_is_correct(window):
    _cell, reading, reduced, _h = window
    got = numbers(reduced)
    assert set(REMOTE) <= set(got)
    assert reference.judge(reduced["numbers"]) is True, got
    assert reduced["failed"] == 0 and got["error_logs"] == 0
    assert got["reads_wrong"] == 0 == got["acks_unreadable"]
    assert got["remote_reads_compared"] >= 10
    assert got["remote_reads_wrong"] == 0
    assert got["remote_snapshots_behind_commit"] == 0
    e2e = reduced["end_to_end"]
    assert e2e["vis_lag_p95_ms"] > 0 and e2e["txn_per_s"] > 0
    detail = reduced["detail"]
    assert detail["vis_lag_samples"] >= 1
    assert len(detail["remote_gate_queued_open_close"]) == 2
    assert set(detail["compiled_in_window"]) == {"jax", "kernels"}


def test_every_prober_read_at_dc2_is_compared(window):
    _cell, reading, reduced, _h = window
    remote = [r for r in reading["records"] if r["dc"] == "dc2"]
    assert remote and all(r["kind"] == "read_only_txn" for r in remote)
    values = sum(len(r["read_keys"]) for r in remote if r["ok"])
    assert numbers(reduced)["remote_reads_compared"] == values
    # each read follows its prober's update of the same keys, at the
    # clock that update returned
    by_client: dict = {}
    for r in reading["records"]:
        by_client.setdefault(r["client"], []).append(r)
    for r in remote:
        recs = by_client[r["client"]]
        update = recs[recs.index(r) - 1]
        assert update["dc"] == "dc1" and update["ok"]
        assert r["read_keys"] == [k for k, _o, _a in update["updates"]]
        assert r["clock_sent"] == update["commit_time"]
        assert r["t_acked"] == update["t_done"] <= r["t_send"]


def test_a_probers_session_stays_at_its_last_commit_at_dc1(window):
    """The read at dc2 does not enter the prober's session: its next
    update at dc1 carries the commit clock of the one before, so it does
    not wait for dc2's heartbeat stamp."""
    _cell, reading, _reduced, _h = window
    by_client: dict = {}
    for r in reading["records"]:
        by_client.setdefault(r["client"], []).append(r)
    pairs = 0
    for recs in by_client.values():
        updates = [r for r in recs if r["dc"] == "dc1" and r["updates"]]
        if not any(r["dc"] == "dc2" for r in recs):
            continue
        for before, after in zip(updates, updates[1:]):
            if before["ok"]:
                assert after["clock_sent"] == before["commit_time"]
                pairs += 1
    assert pairs >= 1


def test_the_read_back_covers_both_dcs(window):
    _cell, reading, reduced, _h = window
    rb = reading["readback"]
    assert len(rb["remote"]) == 1
    assert rb["remote"][0]["compared"] == rb["compared"] > 0
    got = numbers(reduced)
    assert got["remote_acks_read_back"] == got["acks_read_back"]
    assert got["remote_acks_unreadable"] == 0


def test_the_dc2_control_makes_remote_reads_wrong(window):
    _cell, reading, reduced, history = window
    control = reference.control_numbers(history, reading["records"],
                                        reading["readback"])
    assert control["remote_reads_wrong"] > 0
    broken = [(n, control.get(n, v), c, lim)
              for n, v, c, lim in reduced["numbers"]]
    assert reference.judge(broken) is False
    # the remote numbers alone fail it
    remote_only = [(n, control[n] if n.startswith("remote_") and n in
                    control else v, c, lim)
                   for n, v, c, lim in reduced["numbers"]]
    assert reference.judge(remote_only) is False


@pytest.mark.parametrize("name,want", [
    ("depgate_wait_ms_per_remote_txn", 90.0 / 40),
    ("ship_txns_per_frame", 5.0),
    ("remote_apply_ms_per_txn", 2.0),
    ("vis_lag_p50_ms", 1000.0 * (0.1 + 1.0) / 2),
])
def test_a_readers_arithmetic(monkeypatch, name, want):
    from antidote_tpu.obs import prof

    monkeypatch.setattr(prof, "last_capture", lambda: {"spans": {
        "depgate_admit": {"count": 40, "total_s": 0.08}}})
    view = harness.WindowView(
        counters={"depgate_wait_count": 40, "depgate_wait_us": 90_000,
                  "ship_txns": 40,
                  "ship_frames": 8},
        answered={}, update_ops=0, trace={"busy_s": 1.0, "window_s": 3.0},
        vis_lag_s=[0.1, 1.0])
    read = harness._load_reader(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"))
    assert read(view) == pytest.approx(want)


@pytest.mark.parametrize("dcs,want", [(1, 30 / 6), (2, 60 / 12)])
def test_ops_per_flush_counts_each_operation_in_every_dcs_planes(dcs, want):
    # two DCs flush every operation twice: once appended at the origin,
    # once applied at the other; the share holds its one-DC meaning
    view = harness.WindowView(
        counters={"ingest_flushes": 6 * dcs}, answered={}, update_ops=30,
        trace=None, dcs=dcs)
    read = harness._load_reader(os.path.join(
        ROOT, "benchmark", "layer_metrics", "ops_per_flush.py"))
    assert read(view) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_reader_with_nothing_to_read_returns_nothing(monkeypatch, name):
    from antidote_tpu.obs import prof

    monkeypatch.setattr(prof, "last_capture", lambda: None)
    view = harness.WindowView(
        counters={"depgate_wait_count": 0, "depgate_wait_us": 0,
                  "ship_txns": 0,
                  "ship_frames": 0},
        answered={}, update_ops=0, trace=None)
    read = harness._load_reader(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"))
    assert read(view) is None


def test_the_inter_dc_counters_moved_in_the_window(window):
    _cell, reading, _reduced, _h = window
    c = reading["counters"]
    assert c["depgate_wait_count"] > 0 and c["depgate_wait_us"] >= 0
    assert c["ship_txns"] >= c["ship_frames"] > 0


# ------------------------------- the timed path broken underneath at dc2


def _dc2_read_altered(mp):
    from antidote_tpu.interdc.dc import DataCenter

    real = DataCenter.read_objects_static

    def altered(self, clock, objects, properties=None):
        values, vc = real(self, clock, objects, properties)
        if self.node.dc_id == "dc2" and isinstance(values[0], int):
            values = [values[0] + 1] + list(values[1:])
        return values, vc

    mp.setattr(DataCenter, "read_objects_static", altered)


def _remote_apply_left_out(mp):
    """The exchange between DCs left out: dc2 acknowledges what arrives
    and applies none of it (its state stays as it was)."""
    from antidote_tpu.txn.manager import PartitionManager

    mp.setattr(PartitionManager, "apply_remote",
               lambda self, records, *a, **kw: None)


def _half_of_each_remote_txn(mp):
    from antidote_tpu.txn.manager import PartitionManager

    real = PartitionManager.apply_remote

    def half(self, records, *a, **kw):
        ups = [r for r in records if r.kind() == "update"]
        drop = {id(r) for r in ups[len(ups) // 2:]} if len(ups) > 1 \
            else set()
        return real(self, [r for r in records if id(r) not in drop],
                    *a, **kw)

    mp.setattr(PartitionManager, "apply_remote", half)


@pytest.mark.parametrize("fault", [
    _dc2_read_altered, _remote_apply_left_out, _half_of_each_remote_txn])
def test_a_broken_replica_is_not_correct(tiny_root, monkeypatch, fault):
    cpu_hooks(monkeypatch)
    fault(monkeypatch)
    _c, _r, reduced, _h = one_window(tiny_root, 2**31 + 4111)
    got = numbers(reduced)
    assert got["remote_reads_wrong"] + got["remote_acks_unreadable"] > 0
    assert got["reads_wrong"] == 0 == got["acks_unreadable"]
    assert reference.judge(reduced["numbers"]) is False


def test_a_read_at_dc2_that_skips_the_causal_wait_is_not_correct(
        tiny_root, monkeypatch):
    """dc2 answers at its own stable snapshot and forgets the clock the
    prober sends: every value is right at the snapshot it names, and
    the snapshot lies below the prober's own commit."""
    from antidote_tpu.interdc.dc import DataCenter

    cpu_hooks(monkeypatch)
    real = DataCenter.read_objects_static

    def no_wait(self, clock, objects, properties=None):
        if self.node.dc_id != "dc2":
            return real(self, clock, objects, properties)
        coord = self.node.coordinator
        sound = type(coord).snapshot_for
        coord.snapshot_for = lambda _clock, props: sound(coord, None, props)
        try:
            return real(self, clock, objects, properties)
        finally:
            coord.__dict__.pop("snapshot_for", None)

    monkeypatch.setattr(DataCenter, "read_objects_static", no_wait)
    _c, _r, reduced, _h = one_window(tiny_root, 2**31 + 4121)
    got = numbers(reduced)
    assert got["remote_snapshots_behind_commit"] > 0
    assert got["reads_wrong"] == 0 == got["acks_unreadable"]
    assert reference.judge(reduced["numbers"]) is False


# ------------------------------------- one DC builds what it built before


def test_a_configuration_without_dcs_builds_one_node(tiny_root,
                                                     monkeypatch):
    from antidote_tpu.api import AntidoteTPU
    from antidote_tpu.interdc.dc import DataCenter

    cpu_hooks(monkeypatch)
    cell = harness.load_cell(tiny_root, "bb1dc.update90-uniform")
    assert "dcs" not in cell.config and cell.dcs == 1
    dep = harness.Deployment(cell, 5)
    try:
        dep.open()
        assert type(dep.db) is AntidoteTPU
        assert not isinstance(dep.db, DataCenter)
        assert dep.dbs == [dep.db] and dep.servers == [dep.server]
        assert set(dep.counters()) == ONE_DC_COUNTERS
        assert dep.remote_pending() == 0
    finally:
        dep.close()
    reading = {"records": [], "readback": {"compared": 1, "wrong": 0,
                                           "first": [], "keys": []}}
    assert set(reference.control_numbers(
        dep.history, reading["records"], reading["readback"])) == {
        "reads_wrong", "acks_unreadable", "snapshots_behind_session"}
