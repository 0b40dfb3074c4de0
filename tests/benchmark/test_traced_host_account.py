"""A whole rehearsed traced run carries the program's host account
(``obs.prof.last_capture()["host"]``, antidote_tpu/obs/host.py) over
its slice: the collector's generation-2 time the same in the account
and in its ``gc_collect`` spans, the commit path's lock sites, and the
process's CPU at least its Python threads'."""

import contextlib
import gc
import json

import pytest

from test_rehearsal import on_the_cpu  # noqa: F401 — the fixture

from antidote_tpu.obs import prof
from antidote_tpu.obs.spans import tracer
from benchmark import run, trace

def test_a_rehearsed_traced_run_carries_the_host_account(
        tiny_root, on_the_cpu, monkeypatch, capsys):
    """test_rehearsal.py's hooks, update90 (the most lock traffic); a
    generation-2 pass is forced inside the slice, so the account's
    share of it can be held to the spans'."""
    capture = trace.capture

    @contextlib.contextmanager
    def with_a_full_pass(log_dir, slice_s):
        with capture(log_dir, slice_s):
            gc.collect(2)
            yield

    monkeypatch.setattr(trace, "capture", with_a_full_pass)
    rc = run.main(["--workload", "bb1dc.update90-uniform", "--seed",
                   str(2**31 + 39), "--seconds", "4", "--trace", "1"],
                  root=tiny_root)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    host = prof.last_capture()["host"]
    assert host["gc_collections"][2] >= 1
    # the collector's generation-2 time, by the account and by its spans
    full = [s for s in tracer.spans(name="gc_collect")
            if s.args["generation"] == 2]
    assert full
    assert sum(s.dur_us for s in full[-host["gc_collections"][2]:]) \
        / 1e6 == pytest.approx(host["gc_pause_s"][2], rel=0.2, abs=2e-3)
    # the lock was held at the commit path's sites in the slice
    sites = {s.rsplit(".", 1)[-1] for s in host["pm_lock_sites"]}
    assert {"prepare", "commit"} <= sites
    assert host["process_cpu_s"] >= host["python_threads_cpu_s"] - 0.05
