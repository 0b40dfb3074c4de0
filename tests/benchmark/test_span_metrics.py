"""The five per-layer metrics that read the program's own spans
(``obs.prof.last_capture()``, PR 25): each reader's arithmetic on a
hand-built summary, nothing without a trace, an empty sum where the
program holds no capture summary (the parent commit, or a process that
never opened a capture), and all five above 0 after a real capture of
a whole rehearsed run."""

import json
import os

import pytest

from antidote_tpu.obs import prof
from benchmark import harness, run, trace
from benchmark.harness import WindowView

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
RECORDED = os.path.join(ROOT, "benchmark", "data", "v5e_micro.xplane.pb")
NEW = ("frontend_self_ms_per_txn", "serve_queue_wait_p95_ms",
       "manager_wait_ms_per_txn", "device_host_ms_per_dispatch",
       "host_busy_pct")


def row(cat, kind, count, total_s, self_s, p95_s=0.0):
    return {"cat": cat, "kind": kind, "count": count, "total_s": total_s,
            "self_s": self_s, "p95_s": p95_s}


SUMMARY = {
    "length_s": 3.0, "span_count": 0, "dropped": 0,
    "requests": {"ApbStaticReadObjects": {"count": 90, "total_s": 5.0},
                 "ApbStaticUpdateObjects": {"count": 10, "total_s": 2.0}},
    "requests_answered": 100, "host_busy_s": 2.4,
    "spans": {
        "pb_request": row("wire", "root", 100, 7.0, 0.05),
        "pb_decode": row("wire", "work", 100, 0.01, 0.01),
        "pb_encode_send": row("wire", "work", 100, 0.04, 0.04),
        "api_static_read": row("api", "work", 90, 4.0, 0.06),
        "txn_snapshot": row("coordinator", "work", 100, 0.03, 0.03),
        "txn_commit": row("coordinator", "work", 10, 1.0, 0.01),
        # a wait of the front end is no self time of the front end
        "txn_clock_wait": row("coordinator", "wait", 5, 0.5, 0.5),
        "read_serve_queue_wait": row("serve", "wait", 90, 0.9, 0.9,
                                     p95_s=0.021),
        "read_serve_drain": row("device", "work", 50, 2.0, 0.5),
        "pm_lock_wait": row("manager", "wait", 40, 0.10, 0.10),
        "pm_prepared_wait": row("manager", "wait", 2, 0.03, 0.03),
        "device_quiesce_wait": row("manager", "wait", 8, 0.07, 0.07),
        "device_prepare": row("device", "work", 150, 0.06, 0.06),
        "device_dispatch": row("device", "work", 70, 0.14, 0.04),
        "device_fetch": row("device", "wait", 70, 0.35, 0.35),
    },
}
WANT = {
    "frontend_self_ms_per_txn":
        1000.0 * (0.05 + 0.01 + 0.04 + 0.06 + 0.03 + 0.01) / 100,
    "serve_queue_wait_p95_ms": 21.0,
    "manager_wait_ms_per_txn": 1000.0 * (0.10 + 0.03 + 0.07) / 100,
    "device_host_ms_per_dispatch":
        1000.0 * (0.06 + 0.14 + 0.35) / 70,
    "host_busy_pct": 80.0,
}


def reader(name):
    return harness._load_reader(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"))


def view(trace_reading):
    return WindowView(counters={}, answered={}, update_ops=0,
                      trace=trace_reading)


@pytest.mark.parametrize("name", NEW)
def test_a_span_readers_arithmetic(monkeypatch, name):
    monkeypatch.setattr(prof, "last_capture", lambda: SUMMARY)
    assert reader(name)(view({"busy_s": 1.0, "window_s": 3.0})) == \
        pytest.approx(WANT[name])


@pytest.mark.parametrize("name", NEW)
def test_without_a_trace_a_span_reader_returns_nothing(monkeypatch, name):
    monkeypatch.setattr(prof, "last_capture", lambda: SUMMARY)
    assert reader(name)(view(None)) is None


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("program", ["no capture yet", "the parent",
                                     "an empty capture"])
def test_without_a_summary_a_span_reader_returns_an_empty_sum(
        monkeypatch, name, program):
    if program == "no capture yet":
        monkeypatch.setattr(prof, "last_capture", lambda: None)
    elif program == "the parent":    # obs.prof without last_capture
        monkeypatch.delattr(prof, "last_capture")
    else:
        monkeypatch.setattr(prof, "last_capture", lambda: dict(
            SUMMARY, spans={}, requests={}, requests_answered=0,
            host_busy_s=0.0))
    got = reader(name)(view({"busy_s": 1.0, "window_s": 3.0}))
    assert got == 0.0 and isinstance(got, float)


def test_the_new_entries_are_declared_as_the_issue_gives_them():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # by name: where an entry stands in the list is the driver's
    # matter (a later PR adds its own at the end)
    declared = {m["name"]: m for m in bench["per_layer"]}
    assert len(declared) == len(bench["per_layer"])
    assert set(NEW) <= set(declared)
    for name, unit, layer, moves in [
            ("frontend_self_ms_per_txn", "ms/txn",
             "wire server, API, coordinator", "txn_per_s"),
            ("serve_queue_wait_p95_ms", "ms", "read serve",
             "read_p95_ms"),
            ("manager_wait_ms_per_txn", "ms/txn", "partition manager",
             "update_p95_ms"),
            ("device_host_ms_per_dispatch", "ms/dispatch",
             "device planes", "read_p95_ms"),
            ("host_busy_pct", "%", "host process", "txn_per_s")]:
        m = declared[name]
        assert (m["unit"], m["layer"], m["moves"]) == (unit, layer, moves)
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert "workloads" not in m


def test_after_a_real_capture_each_reads_above_zero(tmp_path, monkeypatch,
                                                    capsys):
    """A whole traced run rehearsed on the CPU (test_rehearsal.py's
    hooks; eight clients, so that the partition lock is contended):
    the capture opens through ``obs.prof``, so the program's spans are
    there to read."""
    from bench_tiny import tiny_tree

    from antidote_tpu.txn.manager import PartitionManager

    monkeypatch.setattr(run, "find_chip", lambda chips: None)
    monkeypatch.setattr(harness, "WARM_MIN_PHASES", 1)
    monkeypatch.setattr(harness, "WARM_QUIET_PHASES", 1)
    monkeypatch.setattr(harness, "WARM_PHASE_S", 1.0)
    init = PartitionManager.__init__

    def small_cache(self, *a, **kw):
        init(self, *a, **kw)
        self._val_cache_cap = 64

    monkeypatch.setattr(PartitionManager, "__init__", small_cache)
    monkeypatch.setattr(trace, "xplane_of", lambda log_dir: RECORDED)
    monkeypatch.setitem(trace.PEAKS, "cpu", trace.PEAKS["TPU v5 lite"])
    root = tiny_tree(str(tmp_path / "tree"), clients=8)
    rc = run.main(["--workload", "bb1dc.read90-uniform", "--seed",
                   str(2**31 + 25), "--seconds", "4", "--trace", "1"],
                  root=root)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    for name in NEW:
        assert line["metrics"][name]["value"] > 0, (name, line["metrics"])
    assert line["metrics"]["host_busy_pct"]["value"] <= 100.0
    cap = prof.last_capture()
    assert cap["dropped"] == 0 and cap["requests_answered"] > 50
    assert 0.9 < cap["length_s"] < 2.5          # the slice, 0.3 x 4 s
    spans = cap["spans"]
    for name in ("pb_request", "pb_decode", "api_static_read",
                 "api_static_update", "txn_snapshot", "txn_commit",
                 "read_serve_queue_wait", "read_serve_drain",
                 "read_serve_classify", "read_serve_fold",
                 "device_prepare", "device_dispatch", "device_fetch",
                 "txn_state_read", "pb_encode_send"):
        assert spans.get(name, {}).get("count", 0) > 0, name
    # the program has had no ``device_read`` span since PR 30 (one key
    # is a batch of one and records the three parts), so the reader
    # that named it until PR 35 read what it reads now
    assert "device_read" not in spans
    until_pr_35 = 1000.0 * sum(
        spans.get(n, {}).get("total_s", 0.0) for n in (
            "device_prepare", "device_dispatch", "device_fetch",
            "device_read")) / sum(
        spans.get(n, {}).get("count", 0) for n in (
            "device_dispatch", "device_read"))
    assert line["metrics"]["device_host_ms_per_dispatch"]["value"] == \
        pytest.approx(until_pr_35)
