"""The result line against the contract, for a traced and an untraced
run of every cell; BENCHMARK.json against the contract's limits; each
per-layer reader's arithmetic; and a configuration, a mix, a cell and a
metric added as new files and new entries only."""

import json
import os
import re

import pytest

from benchmark import harness
from benchmark.harness import WindowView

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

TRACE = {"busy_s": 0.4, "window_s": 3.0, "needed_bytes": 1.0e6,
         "hbm_bytes_per_s": 819e9,
         "breakdown": {"device_ops": [["jit_packed_append", 0.3]],
                       "idle_gaps": [["device_flush:counter_pn", 0.1]]}}
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
          "memory_peak_bytes": 1_400_000_000}


#: what a traced slice's span summary holds, for the readers of spans
CAPTURE = {"length_s": 3.0, "requests_answered": 60, "host_busy_s": 2.0,
           "spans": {"depgate_admit": {"cat": "interdc", "kind": "work",
                                       "count": 40, "total_s": 0.08,
                                       "self_s": 0.08, "p95_s": 0.003}}}


def synthetic_reading(cell, traced):
    """What a window hands back: a few transactions of every kind the
    cell's clients send (with probers, each update of theirs followed by
    a read at the second DC), and counter deltas that all moved."""
    records, t = [], 100.0
    kinds = ["read_only_txn"] * 30 + ["update_only_txn"] * 30
    if cell.mix.probers:
        kinds += ["update_only_txn", "remote"] * 10
    for i, kind in enumerate(kinds):
        remote = kind == "remote"
        kind = "read_only_txn" if remote else kind
        rec = {"client": i % 4, "dc": "dc2" if remote else "dc1",
               "kind": kind, "ok": True,
               "read_keys": [] if kind == "update_only_txn" else [1, 2],
               "updates": [] if kind == "read_only_txn"
               else [(1, "increment", 1), (2, "increment", 1)],
               "values": [0, 0], "snapshot_time": 5, "commit_time": 5,
               "clock_sent": 5 if i else None, "aborts": 0,
               "t_send": t, "t_done": t + 0.010 + 0.0001 * i}
        if remote:
            rec["t_acked"] = records[-1]["t_done"]
        records.append(rec)
        t += 0.02
    counters = {k: 7 for k in (
        "read_dispatches", "read_cache_hits", "read_cache_misses",
        "read_serve_groups", "ingest_dispatches", "log_fsyncs",
        "log_group_records", "ingest_flushes", "gc_folds", "kernel_calls",
        "kernel_compile_misses", "jax_programs_compiled")}
    readback = {"compared": 10, "wrong": 0, "first": []}
    if cell.dcs > 1:
        counters.update(depgate_wait_count=40, depgate_wait_us=90_000,
                        ship_txns=40,
                        ship_frames=8)
        readback["remote"] = [dict(readback)]
    return {"seed": 1, "seconds": 10.0, "t_start": 100.0, "t_end": 110.0,
            "setup_s": 120.5, "records": records, "counters": counters,
            "device": DEVICE, "error_logs": 0, "warm_phases": 3,
            "readback": readback,
            "remote_queued": [0, 0] if cell.dcs > 1 else None,
            "trace": TRACE if traced else None}


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("workload", CELLS)
def test_the_last_line_meets_the_contract(workload, traced, monkeypatch):
    from antidote_tpu.obs import prof

    monkeypatch.setattr(prof, "last_capture", lambda: CAPTURE)
    cell = harness.load_cell(ROOT, workload)
    reading = synthetic_reading(cell, traced)
    reduced = harness.reduce_reading(cell, reading)
    line = json.loads(json.dumps(harness.result_line(
        cell, traced, reduced, reading["device"], reading["trace"])))
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == len(reading["records"])
    declared = cell.per_layer if traced else cell.end_to_end
    assert declared
    for m in declared:
        got = line["metrics"][m["name"]]
        assert isinstance(got["value"], float) and got["unit"] == m["unit"]
    assert set(line["metrics"]) == {m["name"] for m in declared}
    dev = line["device"]
    for key in ("platform", "kind", "count", "memory_peak_bytes"):
        assert key in dev
    if traced:
        assert 0 < dev["busy_s"] <= dev["window_s"]
        assert len(line["breakdown"]["device_ops"]) <= 10
        assert len(line["breakdown"]["idle_gaps"]) <= 10
    else:
        assert "busy_s" not in dev and "breakdown" not in line
        assert line["metrics"]["setup_s"]["value"] == 120.5
        assert line["metrics"]["txn_per_s"]["value"] > 0


@pytest.mark.parametrize("trace_reading", [
    None, dict(TRACE, busy_s=0.0), dict(TRACE, busy_s=3.5)])
def test_a_traced_line_never_carries_a_busy_time_out_of_range(
        trace_reading):
    cell = harness.load_cell(ROOT, CELLS[0])
    reduced = harness.reduce_reading(cell, synthetic_reading(cell, True))
    with pytest.raises(harness.BenchError):
        harness.result_line(cell, True, reduced, DEVICE, trace_reading)


def checked(cell, reading):
    from benchmark import reference

    ks = cell.keyspace
    history = reference.PlainHistory(ks, ks.load_values(1))
    for r in reading["records"]:  # answers equal to the reference's
        r["values"] = [history.at(k, r["snapshot_time"])
                       for k in r["read_keys"]]
    reduced = harness.reduce_reading(cell, reading, history)
    return reduced, {n: v for n, v, _c, _l in reduced["numbers"]}


@pytest.mark.parametrize("error", [
    "PbServerError: read failed: batched read blocked on prepared txn",
    "PbServerError: key 7 committed after snapshot",  # retries used up
    "PbError: transport failure: timed out",
])
def test_a_transaction_that_failed_is_not_correct_and_is_in_the_tail(
        error):
    from benchmark import reference

    cell = harness.load_cell(ROOT, CELLS[0])
    reading = synthetic_reading(cell, False)
    sound, got = checked(cell, reading)
    assert got["failed"] == 0 and reference.judge(sound["numbers"])
    for stalled in reading["records"][:2]:
        stalled.update(ok=False, error=error, values=None,
                       t_done=stalled["t_send"] + 5.012)
    reduced, got = checked(cell, reading)
    assert reduced["failed"] == 2 == got["failed"]
    assert got["reads_wrong"] == 0
    assert reference.judge(reduced["numbers"]) is False
    # two reads of thirty reach past the 95th percentile's rank: the 5 s
    # they waited show in the tail, and they are no answered transactions
    assert reduced["end_to_end"]["read_p95_ms"] \
        > 10 * sound["end_to_end"]["read_p95_ms"]
    assert reduced["detail"]["read_max_ms"] == pytest.approx(5012.0)
    assert reduced["end_to_end"]["txn_per_s"] \
        < sound["end_to_end"]["txn_per_s"]


def test_a_snapshot_behind_the_session_is_not_correct():
    from benchmark import reference

    cell = harness.load_cell(ROOT, CELLS[0])
    reading = synthetic_reading(cell, False)
    reading["records"][3]["clock_sent"] = 6  # answered at 5
    reduced, got = checked(cell, reading)
    assert got["snapshots_behind_session"] == 1
    assert got["session_clocks_sent"] == len(reading["records"]) - 1
    assert reference.judge(reduced["numbers"]) is False


def test_retried_aborts_are_counted_beside_the_line():
    cell = harness.load_cell(ROOT, CELLS[0])
    reading = synthetic_reading(cell, False)
    reading["records"][40]["aborts"] = 2
    reduced, got = checked(cell, reading)
    assert reduced["detail"]["aborts_retried"] == 2
    assert reduced["failed"] == 0 == got["failed"]


def test_every_cell_reports_what_the_contract_asks():
    for workload in CELLS:
        cell = harness.load_cell(ROOT, workload)
        names = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        e2e = set(names)
        assert all(m["moves"] in e2e for m in cell.per_layer)


def test_benchmark_json_keeps_the_contracts_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536
    runs = 2 + 14 * 24  # the full 24 cells must still fit
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers_named = set()
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and "bound" not in m
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        layers_named.add(m["layer"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        for w in m.get("workloads", ()):
            assert w in CELLS
    used = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        used.add(w["config"])
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(len(BENCH["workloads"]) // 2, 1)
    files = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used and len(c["source"]) <= 200
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        with open(os.path.join(ROOT, c["file"])) as f:
            doc = json.load(f)
        assert doc["reduced"] == c["reduced"] and doc["source"] == c["source"]
        assert all(NAME.match(k) for k in c["reduced"])
        files.add(c["file"])
    assert len(files) == len(BENCH["configs"])
    # PERF.md's list of layers names every layer the metrics give
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    assert all(layer in perf for layer in layers_named)


# ------------------------------------------------ the readers' arithmetic


def view(**changes):
    base = dict(
        counters={"read_cache_hits": 30, "read_cache_misses": 70,
                  "read_dispatches": 50, "kernel_compile_misses": 2,
                  "jax_programs_compiled": 1, "ingest_flushes": 20,
                  "log_fsyncs": 4, "log_group_records": 100},
        answered={"read_only_txn": 200, "update_only_txn": 25},
        update_ops=250,
        trace=dict(TRACE))
    base.update(changes)
    return WindowView(**base)


def reader(name):
    return harness._load_reader(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"))


@pytest.mark.parametrize("name,want", [
    ("read_cache_hit_pct", 30.0),
    ("read_dispatches_per_read", 0.25),
    ("compiles_in_window", 3.0),
    ("ops_per_flush", 12.5),
    ("kernels_roofline", 100.0 * 1.0e6 / 819e9 / 0.4),
    ("device_idle_pct", 100.0 * (1 - 0.4 / 3.0)),
])
def test_a_readers_arithmetic(name, want):
    assert reader(name)(view()) == pytest.approx(want)


@pytest.mark.parametrize("name,empty", [
    ("read_cache_hit_pct", dict(counters={
        "read_cache_hits": 0, "read_cache_misses": 0})),
    ("read_dispatches_per_read", dict(answered={})),
    ("ops_per_flush", dict(counters={"ingest_flushes": 0})),
    ("kernels_roofline", dict(trace=None)),
    ("kernels_roofline", dict(trace=dict(TRACE, needed_bytes=0))),
    ("device_idle_pct", dict(trace=None)),
])
def test_a_reader_with_nothing_to_read_returns_nothing(name, empty):
    # never a 0 in place of a share: the harness leaves the metric out
    assert reader(name)(view(**empty)) is None


def test_every_declared_metric_has_a_reader_of_its_own():
    for m in BENCH["per_layer"]:
        path = os.path.join(ROOT, "benchmark", "layer_metrics",
                            m["name"] + ".py")
        assert os.path.exists(path), path
        assert callable(harness._load_reader(path))


# ------------------------------------------- adding takes new files only


def test_a_later_pr_adds_files_and_entries_and_edits_none(tiny_root):
    root = tiny_root
    before = {}
    for dirpath, _dirs, names in os.walk(os.path.join(root, "benchmark")):
        for n in names:
            with open(os.path.join(dirpath, n), "rb") as f:
                before[os.path.join(dirpath, n)] = f.read()
    bdir = os.path.join(root, "benchmark")
    with open(os.path.join(bdir, "configs", "bb1dc.json")) as f:
        cfg = json.load(f)
    cfg.update(name="ycsb8", source="a new public deployment",
               partitions=8)
    with open(os.path.join(bdir, "configs", "ycsb8.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bdir, "traffic", "read90-uniform.json")) as f:
        mix = json.load(f)
    mix.update(name="read50-uniform", clients=8, retry_for_s=2,
               operations={"read_only_txn": 1, "update_only_txn": 1})
    with open(os.path.join(bdir, "traffic", "read50-uniform.json"),
              "w") as f:
        json.dump(mix, f)
    with open(os.path.join(bdir, "layer_metrics",
                           "gc_folds_per_s.py"), "w") as f:
        f.write('"""A new layer metric."""\n\n\ndef read(w):\n'
                '    return w.counters["gc_folds"] / 10.0\n')
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "ycsb8", "source": cfg["source"], "reduced": [],
        "file": "benchmark/configs/ycsb8.json", "why": "new"})
    bench["workloads"].append({
        "name": "ycsb8.read50-uniform", "config": "ycsb8",
        "traffic": "read50-uniform", "chips": 1, "why": "new"})
    bench["per_layer"].append({
        "name": "gc_folds_per_s", "unit": "1/s", "better": "lower",
        "source": "program_counter", "layer": "device planes",
        "moves": "update_p95_ms", "workloads": ["ycsb8.read50-uniform"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    cell = harness.load_cell(root, "ycsb8.read50-uniform")
    assert cell.keyspace.n_partitions == 8
    assert (cell.mix.clients, cell.mix.retry_for_s) == (8, 2.0)
    assert cell.mix.operations == {"read_only_txn": 1,
                                   "update_only_txn": 1}
    # it reports the new metric, and every metric declared for all
    # cells that report what it moves; an old cell does not report the
    # new one
    names = {m["name"] for m in cell.per_layer}
    e2e = {m["name"] for m in cell.end_to_end}
    for_all = {m["name"] for m in bench["per_layer"]
               if "workloads" not in m and m["moves"] in e2e}
    assert names == for_all | {"gc_folds_per_s"}
    reading = synthetic_reading(cell, True)
    reduced = harness.reduce_reading(cell, reading)
    line = harness.result_line(cell, True, reduced, DEVICE, TRACE)
    assert line["metrics"]["gc_folds_per_s"] == {"value": 0.7,
                                                 "unit": "1/s"}
    old = harness.load_cell(root, CELLS[0])
    assert "gc_folds_per_s" not in {m["name"] for m in old.per_layer}
    for path, content in before.items():
        with open(path, "rb") as f:
            assert f.read() == content, f"{path} was edited"


def test_an_unknown_cell_or_a_missing_file_is_an_error(tiny_root):
    with pytest.raises(harness.BenchError, match="unknown workload"):
        harness.load_cell(tiny_root, "nope.nope")
    os.unlink(os.path.join(tiny_root, "benchmark", "traffic",
                           "read90-uniform.json"))
    with pytest.raises(harness.BenchError, match="traffic"):
        harness.load_cell(tiny_root, CELLS[0])
