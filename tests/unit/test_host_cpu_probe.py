"""tools/host_cpu_probe.py: a rehearsed window on the CPU gives the
program's whole host account — counts and shares, never rates — and a
tree whose program keeps none is refused.  The account itself (the
lock's sites, the threads' kinds, the collector) is
tests/unit/test_host_account.py's."""

import json

import pytest

from tools import host_cpu_probe as probe


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
    kinds = acc["thread_cpu_s"]
    assert kinds["handlers"] > 0
    assert "device-flusher" in kinds
    assert 0 < acc["python_threads_cpu_s"] <= acc["process_cpu_s"] + 0.05
    assert acc["cpu_ms_per_txn"] == pytest.approx(
        1000 * acc["python_threads_cpu_s"] / acc["answered"])
    assert acc["pm_lock_hold_ms_per_txn"] == pytest.approx(
        1000 * acc["pm_lock"]["held_s"] / acc["answered"])
    # the commit path's sites and the read's capture took the lock, each
    # named by the function that acquired
    sites = {site.rsplit(".", 1)[-1] for site in acc["pm_lock_sites"]}
    assert {"prepare", "commit", "stage_group", "read_many_begin"} <= sites
    for d in acc["pm_lock_sites"].values():
        assert d["held_s"] >= 0 and d["waited_s"] >= 0
        assert d["holds"] >= d["sleeps"]
    assert acc["pm_lock"]["holds"] == sum(
        d["holds"] for d in acc["pm_lock_sites"].values())
    # a process that served a node before may run with the collector's
    # thresholds raised (runtime.tune_runtime), and pass none in 2 s
    assert acc["gc_pause_ms_per_s"] == pytest.approx(
        1000 * sum(acc["gc_pause_s"].values()) / acc["length_s"])
    assert all(n >= 0 for n in acc["gc_collections"].values())
    assert "| pm._lock site |" in text and "| handlers |" in text
    # the flusher's routine flushes left the lock, and are counted so
    fl = acc["flushes"]
    assert fl["clean"] > 0 and fl["inflight_waits"] >= 0
    assert "flushes: clean" in text


def test_a_rehearsed_durable_window_counts_the_redemptions(capsys):
    """Under ``sync_log`` every commit redeems a ticket a partition it
    wrote; the account says what each met, beside the fsyncs."""
    probe.main(["--workload", "bb1dc-sync.update90-uniform", "--seed",
                "2140000533", "--seconds", "2", "--partitions", "2",
                "--keys-per-partition", "1024", "--clients", "3"])
    text = capsys.readouterr().out
    acc = json.loads(text.strip().splitlines()[-1][len("PROBE "):])
    assert acc["failed"] == 0 and acc["answered"] > 0
    lg = acc["log"]
    assert lg["fsyncs"] > 0 and lg["records"] >= lg["fsyncs"]
    assert set(lg) == {"fsyncs", "records", "covered", "led", "woken"}
    assert lg["led"] > 0
    assert min(lg["covered"], lg["woken"]) >= 0
    assert "tickets redeemed: covered" in text


def test_a_tree_without_the_account_is_refused(tmp_path, monkeypatch):
    """A parent before the account is read with the tool as it was: the
    tool says so instead of reading half of it."""
    import sys

    import antidote_tpu.obs

    monkeypatch.setattr(sys, "path", list(sys.path))
    monkeypatch.delattr(antidote_tpu.obs, "host")
    monkeypatch.setitem(sys.modules, "antidote_tpu.obs.host", None)
    with pytest.raises(SystemExit, match="as of commit bab25bb"):
        probe.main(["--workload", "bb1dc.update90-uniform", "--tree",
                    str(tmp_path)])
