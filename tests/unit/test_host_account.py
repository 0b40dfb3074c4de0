"""The host process's account (antidote_tpu/obs/host.py): the partition
lock's holds by acquiring site, the collector's passes, CPU by process
and by thread kind, the account of a profiler capture, one clock with
the profiler's trace, and the registry's families read when scraped.
Counts, never timings: where a bound names a time it is a wide one."""

import gc
import glob
import os
import threading
import time

import pytest

from antidote_tpu import stats
from antidote_tpu.obs import host, prof
from antidote_tpu.obs.spans import tracer
from antidote_tpu.txn.manager import _SiteCondition, _TimedLock

# ------------------------------------------------- a capture's account


def spin(seconds):
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def test_a_capture_carries_what_the_process_did_between_its_bounds(
        tmp_path):
    cond = _SiteCondition()

    def stage():
        with cond:
            pass

    stage()                         # before the capture: not its
    with prof.profile(str(tmp_path)):
        for _ in range(3):
            stage()
        spin(0.02)
    stage()                         # after it: not its either
    hs = prof.last_capture()["host"]
    assert hs["pm_lock_sites"][stage.__qualname__]["holds"] == 3
    assert hs["pm_lock"]["holds"] >= 3
    assert hs["length_s"] >= 0.02
    assert hs["python_threads_cpu_s"] > 0
    assert hs["native_cpu_s"] == pytest.approx(
        hs["process_cpu_s"] - hs["python_threads_cpu_s"])
    assert set(hs["gc_pause_s"]) == set(hs["gc_collections"]) == {0, 1, 2}


def test_a_difference_lists_only_the_sites_that_took_the_lock():
    cond = _SiteCondition()

    def idle_site():
        with cond:
            pass

    def busy_site():
        with cond:
            pass

    idle_site()
    a = host.account()
    busy_site()
    busy_site()
    d = host.difference(a, host.account())
    assert busy_site.__qualname__ in d["pm_lock_sites"]
    assert idle_site.__qualname__ not in d["pm_lock_sites"]
    row = d["pm_lock_sites"][busy_site.__qualname__]
    assert (row["holds"], row["waits"], row["sleeps"]) == (2, 0, 0)
    assert row["held_s"] >= 0 and row["waited_s"] == row["slept_s"] == 0


def test_a_family_read_when_scraped_shows_zero_before_any_sample():
    fam = stats.ReadCounter("antidote_test_read_total", "a test family",
                            ("site",), lambda: {})
    name, value = list(fam.expose())[-1].split()
    assert name.startswith("antidote_test_read_total") and float(value) == 0
    assert fam.value(site="anywhere") == 0.0
    box = {("a",): 2.0}
    fam = stats.ReadCounter("antidote_test_read_total", "a test family",
                            ("site",), lambda: dict(box))
    box[("b",)] = 5.0               # read at scrape, not at creation
    assert fam.value(site="b") == 5.0
    assert any(line.startswith('antidote_test_read_total{site="b"} 5')
               for line in fam.expose())


# ------------------------------------------- the partition lock's sites


def test_a_hold_and_a_wait_go_to_the_function_that_acquired():
    cond = _SiteCondition()
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
    assert set(cond.sites) == {holder.__qualname__, waiter.__qualname__}
    h, w = cond.sites[holder.__qualname__], cond.sites[waiter.__qualname__]
    assert (h.holds, h.waits) == (1, 0) and h.held_ns >= 40_000_000
    assert (w.holds, w.waits) == (1, 1) and w.waited_ns > 0
    assert w.held_ns < h.held_ns


def test_a_sleep_on_the_condition_is_neither_hold_nor_wait():
    cond = _SiteCondition()
    ready = []

    def sleeper():
        with cond:
            with cond:              # a wait inside a re-entrant hold
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
    s = cond.sites[sleeper.__qualname__]
    assert s.sleeps >= 1 and s.slept_ns >= 40_000_000
    assert s.holds == s.sleeps + 1 and s.held_ns < 40_000_000
    me = cond.sites[
        test_a_sleep_on_the_condition_is_neither_hold_nor_wait
        .__qualname__]
    assert (me.holds, me.waits) == (1, 0)


def test_a_site_reached_through_locked_is_named_by_its_caller():
    class Manager:
        def __init__(self):
            self._lock = _SiteCondition()
            self._locked = _TimedLock(self._lock, 3)

        def commit(self):
            with self._locked:
                with self._lock:    # re-entrant: the same hold
                    pass

    pm = Manager()
    pm.commit()
    assert set(pm._lock.sites) == {Manager.commit.__qualname__}
    assert pm._lock.sites[Manager.commit.__qualname__].holds == 1
    assert not any(site.rsplit(".", 1)[-1] in ("__enter__", "acquire")
                   for site in host.lock_sites())


def test_the_condition_keeps_the_lock_contract():
    cond = _SiteCondition()
    with pytest.raises(RuntimeError):
        cond.release()
    with pytest.raises(RuntimeError):
        cond.wait(0.01)
    taken = threading.Event()
    let_go = threading.Event()

    def other():
        with cond:
            taken.set()
            let_go.wait(5)

    t = threading.Thread(target=other)
    t.start()
    assert taken.wait(5)
    assert cond.acquire(False) is False
    assert cond.acquire(timeout=0.02) is False
    with pytest.raises(RuntimeError):
        cond.release()              # not ours to give back
    let_go.set()
    t.join(5)
    assert cond.acquire(timeout=1.0) is True
    assert cond.wait(0.01) is False     # a timeout, the lock still ours
    cond.release()
    me = cond.sites[test_the_condition_keeps_the_lock_contract
                    .__qualname__]
    # the two refused attempts count nothing; the one taken after them
    # waited for nobody
    assert (me.holds, me.waits, me.sleeps) == (2, 0, 1)


def test_the_sums_over_partitions_are_read_by_site():
    a, b = _SiteCondition(), _SiteCondition()

    def stage():
        with a:
            pass
        with b:
            pass

    before = host.lock_sites().get(stage.__qualname__)
    stage()
    stage()
    after = host.lock_sites()[stage.__qualname__]
    assert before is None and after["holds"] == 4
    reg = stats.registry
    assert reg.pm_lock_holds.value(site=stage.__qualname__) == 4
    assert reg.pm_lock_held.value(site=stage.__qualname__) == \
        pytest.approx(after["held_ns"] / 1e9)
    text = reg.exposition()
    assert f'antidote_pm_lock_holds_total{{site="{stage.__qualname__}"}} 4' \
        in text


# ------------------------------------------------------------ CPU by kind


@pytest.mark.parametrize("name, kind", [
    ("Thread-12 (process_request_thread)", "handlers"),
    ("Thread-3 (serve_forever)", "serve_forever"),
    ("device-flusher", "device-flusher"),
    ("warm:counter_pn", "warm:counter_pn"),
    ("ckpt-3", "ckpt"), ("MainThread", "MainThread")])
def test_threads_of_one_pool_share_a_kind(name, kind):
    assert host.thread_kind(name) == kind


def test_a_thread_that_ends_keeps_its_cpu_in_its_kind():
    go = threading.Event()

    def work():
        spin(0.03)
        go.wait(5)

    t = threading.Thread(target=work, name="probe-worker-1")
    t.start()
    time.sleep(0.05)
    during = host.thread_cpu()["probe-worker"]
    go.set()
    t.join(5)
    after = host.thread_cpu()["probe-worker"]
    assert during > 0 and after >= during
    assert host.thread_cpu()["probe-worker"] == after


def test_the_process_cpu_has_one_source():
    text = stats.registry.exposition()
    samples = [line for line in text.splitlines()
               if line.startswith("process_cpu_seconds_total")]
    assert len(samples) == 1
    assert float(samples[0].split()[1]) == pytest.approx(
        time.process_time(), abs=0.5)
    assert "# TYPE process_cpu_seconds_total counter" in text
    assert sum(line.startswith("process_cpu_seconds_total ")
               for line in stats.process_metrics()) == 1


# -------------------------------------------------------- the collector


def test_a_generation_2_pass_in_a_window_is_counted_and_timed():
    host.install()
    a = host.account()
    gc.collect(2)
    d = host.difference(a, host.account())
    # (another test's server thread may pass too, never fewer)
    assert d["gc_collections"][2] >= 1
    assert d["gc_pause_s"][2] > 0
    text = stats.registry.exposition()
    assert 'antidote_gc_collections_total{generation="2"}' in text
    assert stats.registry.gc_collections.value(generation="2") >= 1


def test_a_pass_outside_any_recorded_span_records_none():
    host.install()
    tracer.clear()
    assert tracer.current() is None and not tracer.capturing
    gc.collect(1)
    assert [s for s in tracer.spans(name="gc_collect")
            if s.tid == threading.get_ident()] == []


def test_a_pass_inside_a_capture_is_a_span_under_the_running_one(
        tmp_path):
    host.install()
    with prof.profile(str(tmp_path)):
        with tracer.span("holding_the_thread", "t"):
            gc.collect(2)
    cap = prof.last_capture()
    outer = tracer.spans(name="holding_the_thread")[-1]
    passes = [s for s in tracer.spans(name="gc_collect")
              if s.parent_id == outer.span_id]
    assert [p.args["generation"] for p in passes].count(2) == 1
    s = next(p for p in passes if p.args["generation"] == 2)
    assert "collected" in s.args
    assert s.kind == "work" and s.tid == outer.tid
    assert 0 <= s.dur_us <= outer.dur_us
    row = cap["spans"]["gc_collect"]
    assert row["count"] >= 1 and row["kind"] == "work"
    # the capture's account and its spans agree on the collector
    hs = cap["host"]
    assert hs["gc_collections"][2] >= 1
    assert row["total_s"] == pytest.approx(
        sum(hs["gc_pause_s"].values()), abs=2e-3, rel=0.2)
    # the pass is its own: the span it stopped loses it from its self time
    parent = cap["spans"]["holding_the_thread"]
    assert parent["self_s"] == pytest.approx(
        (outer.dur_us - sum(p.dur_us for p in passes)) / 1e6, abs=1e-5)


# ------------------------------------------ one clock with the device trace


def test_a_span_and_its_annotation_share_the_traces_clock(tmp_path):
    """The ``.xplane.pb``'s events are offsets from the capture's
    ``profile_start_time`` (the ``Task Environment`` plane, epoch ns):
    on that base a span's epoch start and its annotation's agree, so a
    wait span, which holds no annotation, lays over the device's gaps
    as it is."""
    import jax

    host.install()
    with prof.profile(str(tmp_path)):
        time.sleep(0.02)
        with tracer.span("clock_probe", "t"):
            gc.collect(2)
            time.sleep(0.01)
        time.sleep(0.02)
    span = tracer.spans(name="clock_probe")[-1]
    path = sorted(glob.glob(os.path.join(str(tmp_path), "**",
                                         "*.xplane.pb"), recursive=True))
    data = jax.profiler.ProfileData.from_file(path[-1])
    base = None
    events = {}
    for plane in data.planes:
        for key, value in plane.stats:
            if key == "profile_start_time":
                base = int(value)
        for line in plane.lines:
            for e in line.events:
                if e.name in ("clock_probe", "gc_collect"):
                    events.setdefault(e.name, []).append(e)
    assert base is not None and len(events["clock_probe"]) == 1
    ann = events["clock_probe"][0]
    assert abs(base + ann.start_ns - span.start_us * 1000) < 1e6
    assert abs(ann.duration_ns - span.dur_us * 1000) < 1e6
    # the collector's pass is named in the trace, inside the span's
    gc_span = [s for s in tracer.spans(name="gc_collect")
               if s.parent_id == span.span_id][-1]
    near = [e for e in events["gc_collect"]
            if abs(base + e.start_ns - gc_span.start_us * 1000) < 1e6]
    assert near and ann.start_ns <= near[0].start_ns <= \
        ann.start_ns + ann.duration_ns


# ------------------------------------------------------------- stress


def test_many_threads_keep_the_lock_exclusive_and_the_table_whole():
    """More threads than cores, a switch every microsecond: the table is
    written by the holder only, so no hold is lost, and the lock still
    excludes (a lost update of ``inside`` would show)."""
    import sys

    cond = _SiteCondition()
    timed = _TimedLock(cond, 0)
    n_threads, rounds = 2 * (os.cpu_count() or 4), 300
    inside = [0]

    def through_with():
        with cond:
            inside[0] += 1

    def through_timed():
        with timed:
            with cond:
                inside[0] += 1

    def through_wait():
        with cond:
            cond.wait(0)
            inside[0] += 1

    def worker(i):
        for r in range(rounds):
            (through_with, through_timed, through_wait)[(i + r) % 3]()

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert inside[0] == n_threads * rounds
    sites = cond.sites
    sleeps = sites[through_wait.__qualname__].sleeps
    assert sleeps == sum(1 for i in range(n_threads) for r in range(rounds)
                         if (i + r) % 3 == 2)
    # a wait splits its hold in two
    assert sum(s.holds for s in sites.values()) == \
        n_threads * rounds + sleeps
    assert cond._owner is None and cond._depth == 0


def test_a_pass_inside_the_tracers_own_lock_neither_deadlocks_nor_is_lost(
        tmp_path):
    """With the collector's threshold at its lowest a pass starts inside
    every allocation, the tracer's critical sections among them; its
    span is queued without the lock and reaches the ring."""
    host.install()
    done = threading.Event()
    box = {}

    def traced_work():
        old = gc.get_threshold()
        gc.set_threshold(1)
        try:
            with prof.profile(str(tmp_path)):
                for i in range(300):
                    with tracer.span("outer", "t", i=i):
                        with tracer.span("inner", "t"):
                            [[j] for j in range(20)]
            box["cap"] = prof.last_capture()
        finally:
            gc.set_threshold(*old)
            done.set()

    t = threading.Thread(target=traced_work, daemon=True)
    t.start()
    assert done.wait(60), "a collection inside the tracer deadlocked"
    cap = box["cap"]
    assert cap["spans"]["outer"]["count"] == 300
    hs = cap["host"]
    # every pass of the capture on any thread is a span of the ring
    assert cap["spans"]["gc_collect"]["count"] >= sum(
        hs["gc_collections"].values()) * 0.9 > 100
    assert cap["dropped"] == 0
