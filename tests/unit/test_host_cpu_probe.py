"""tools/host_cpu_probe.py (PR 34): the lock probe books a hold and a
wait to the function that acquired, through ``_TimedLock`` too, and a
rehearsed window on the CPU gives the whole account — counts and
shares, never rates."""

import json
import threading
import time

import pytest

from antidote_tpu.txn.manager import _TimedLock
from tools import host_cpu_probe as probe


def test_a_hold_and_a_wait_go_to_the_function_that_acquired():
    stats: dict = {}
    cond = probe.ProbedCondition(threading.Condition(), stats)
    timed = _TimedLock(cond, 0)
    inside = threading.Event()

    def holder():
        with cond:
            with cond:              # re-entrant: one hold
                inside.set()
                time.sleep(0.05)

    def waiter():
        with timed:                 # the request path's form
            pass

    t = threading.Thread(target=holder)
    t.start()
    assert inside.wait(5)
    waiter()
    t.join(5)
    assert not t.is_alive()
    assert set(stats) == {"holder", "waiter"}
    h, w = stats["holder"], stats["waiter"]
    assert (h.holds, h.waits) == (1, 0) and h.hold_s >= 0.04
    assert (w.holds, w.waits) == (1, 1) and 0.0 < w.wait_s <= w.wait_max
    assert w.hold_s < h.hold_s


def test_a_sleep_on_the_condition_is_neither_hold_nor_wait():
    stats: dict = {}
    cond = probe.ProbedCondition(threading.Condition(), stats)
    ready = []

    def sleeper():
        with cond:
            while not ready:
                cond.wait(5)

    t = threading.Thread(target=sleeper)
    t.start()
    time.sleep(0.05)
    with cond:                      # free while the sleeper sleeps
        ready.append(1)
        cond.notify_all()
    t.join(5)
    assert not t.is_alive()
    s = stats["sleeper"]
    assert s.sleeps >= 1 and s.sleep_s >= 0.04
    assert s.holds == s.sleeps + 1 and s.hold_s < 0.04
    assert stats[test_a_sleep_on_the_condition_is_neither_hold_nor_wait
                 .__name__].waits == 0


@pytest.mark.parametrize("name, kind", [
    ("Thread-12 (process_request_thread)", "handlers"),
    ("Thread-3 (serve_forever)", "serve_forever"),
    ("device-flusher", "device-flusher"),
    ("warm:counter_pn", "warm:counter_pn"),
    ("ckpt-3", "ckpt"), ("MainThread", "MainThread")])
def test_threads_of_one_pool_share_a_kind(name, kind):
    assert probe.thread_kind(name) == kind


def test_a_rehearsed_window_gives_the_whole_account(tmp_path, capsys):
    out = tmp_path / "probe.json"
    rc = probe.main(["--workload", "bb1dc.update90-uniform", "--seed",
                     "2140000511", "--seconds", "2", "--partitions", "2",
                     "--keys-per-partition", "1024", "--clients", "3",
                     "--out", str(out)])
    text = capsys.readouterr().out
    acc = json.loads(out.read_text())
    # a 2 s window of a keyspace the value cache holds may reach the
    # device with no read: that limit alone may be missed here
    assert set(acc["not_kept"]) <= {"device_read_dispatches",
                                    "read_cache_misses"}, text
    assert rc == (0 if acc["correct"] else 1)
    assert acc["failed"] == 0 and acc["answered"] > 0
    kinds = acc["thread_kinds"]
    # at least: a handler another test of this process left is counted
    assert kinds["handlers"]["threads"] >= 3
    assert kinds["handlers"]["cpu_s"] > 0
    assert "device-flusher" in kinds
    assert 0 < acc["python_threads_cpu_s"] <= acc["process_cpu_s"] + 0.05
    assert acc["cpu_ms_per_txn"] == pytest.approx(
        1000 * acc["python_threads_cpu_s"] / acc["answered"])
    # the commit path's sites and the read's capture took the lock
    assert {"prepare", "commit", "stage_group",
            "read_many_begin"} <= set(acc["locks"])
    for d in acc["locks"].values():
        assert d["hold_s"] >= 0 and d["wait_s"] >= 0
        assert d["wait_max"] <= d["wait_s"] + 1e-9
    assert "| pm._lock site |" in text and "| handlers |" in text
