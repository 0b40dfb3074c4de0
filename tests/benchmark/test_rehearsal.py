"""A whole run rehearsed on the CPU at a tiny size, for both ``--trace``
values: the harness's look for a chip is replaced here (the program has
no switch for it), the value cache is shrunk so that reads reach the
planes, and in a traced run the recorded v5e trace stands in for the
capture, since a CPU capture holds no device plane."""

import json
import os

import pytest

from benchmark import harness, run, trace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
RECORDED = os.path.join(ROOT, "benchmark", "data", "v5e_micro.xplane.pb")
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


@pytest.fixture
def on_the_cpu(monkeypatch):
    """The test-side hooks of a rehearsal."""
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


def last_line(capsys):
    out = capsys.readouterr()
    lines = out.out.strip().splitlines()
    return json.loads(lines[-1]), out


def test_without_a_chip_it_exits_non_zero_and_prints_no_result(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", BENCH["workloads"][0]["name"]])
    assert e.value.code == 2
    out = capsys.readouterr()
    assert out.out == "" and "no TPU" in out.err


@pytest.mark.parametrize("traced", [0, 1])
@pytest.mark.parametrize("workload",
                         [w["name"] for w in BENCH["workloads"]])
def test_a_whole_run_of_every_cell(tiny_root, on_the_cpu, capsys,
                                   workload, traced):
    rc = run.main(["--workload", workload, "--seed", str(2**31 + 77),
                   "--seconds", "2", "--trace", str(traced)],
                  root=tiny_root)
    line, out = last_line(capsys)
    assert rc == 0
    compared = {k: v["value"] for k, v in line["compared"].items()}
    assert line["correct"] is True, compared
    assert compared["reads_wrong"] == 0 == compared["acks_unreadable"]
    assert compared["reads_compared"] > 100
    assert compared["snapshots_behind_session"] == 0
    assert compared["session_clocks_sent"] > 50
    assert compared["device_read_dispatches"] > 0
    assert line["failed"] == 0 and line["attempted"] > 50
    cell = next(w for w in BENCH["workloads"] if w["name"] == workload)
    section = BENCH["per_layer"] if traced else BENCH["end_to_end"]
    for m in section:
        if "workloads" in m and workload not in m["workloads"]:
            assert m["name"] not in line["metrics"]
    declared = harness.load_cell(tiny_root, workload)
    declared = declared.per_layer if traced else declared.end_to_end
    names = {m["name"] for m in declared}
    assert set(line["metrics"]) == names
    if traced:
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        for name in {"compiles_in_window", "ops_per_flush",
                     "kernels_roofline", "device_idle_pct"} & names:
            assert line["metrics"][name]["value"] >= 0, name
        if "kernels_roofline" in names:
            assert 0 < line["metrics"]["kernels_roofline"]["value"] < 100
    else:
        for name in {"txn_per_s", "update_p95_ms", "setup_s"} & names:
            assert line["metrics"][name]["value"] > 0, name
        assert {"txn_per_s", "setup_s"} <= names
        assert "busy_s" not in line["device"]
    assert cell["chips"] == 1
    # the numbers compared close standard error, each beside its limit
    tail = [t for t in out.err.strip().splitlines()][-len(compared):]
    assert all(t.startswith("compared ") and "limit" in t for t in tail)
