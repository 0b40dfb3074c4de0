"""``DeviceFlusher.stop()`` against a ``schedule()`` that races it (PR 26):
a node that closes while a commit is still scheduling a flush must not
wait for ever.  Before the repair the racing ``schedule()`` started a
second thread on the same queue, that thread took the stopping thread's
sentinel, and ``stop()`` joined a thread that slept in ``get()`` for
good: ``tests/cluster/test_causal_federation.py``'s restart never came
back ("writer starved by certification aborts")."""

import threading

from antidote_tpu.txn.manager import DeviceFlusher


class _Pm:
    """What the flusher touches of a partition manager."""

    def flush_scheduled(self, plane):
        plane.flush_gc_now()


class _Plane:
    def __init__(self, hold=None):
        self.hold, self.entered, self.flushed = hold, threading.Event(), 0

    def flush_gc_now(self):
        self.entered.set()
        if self.hold is not None:
            assert self.hold.wait(5)
        self.flushed += 1


def _stop_in_a_thread(flusher):
    t = threading.Thread(target=flusher.stop, daemon=True)
    t.start()
    return t


def test_stop_returns_when_a_schedule_races_it():
    flusher, pm = DeviceFlusher(), _Pm()
    release = threading.Event()
    busy, late = _Plane(hold=release), _Plane()
    flusher.schedule(pm, busy)
    assert busy.entered.wait(5)  # the thread is inside a flush
    stopping = _stop_in_a_thread(flusher)
    # stop() has taken the thread when schedule() can start another
    while flusher._thread is not None:
        stopping.join(0.001)
    flusher.schedule(pm, late)
    release.set()
    stopping.join(5)
    assert not stopping.is_alive(), "stop() waits for a thread asleep in get()"
    assert busy.flushed == 1
    # what was scheduled after the stop is still flushed, by a thread
    # that a second stop() ends
    assert late.entered.wait(5)
    second = _stop_in_a_thread(flusher)
    second.join(5)
    assert not second.is_alive() and late.flushed == 1


def test_stop_drains_what_was_scheduled_before_it():
    flusher, pm = DeviceFlusher(), _Pm()
    planes = [_Plane() for _ in range(5)]
    for plane in planes:
        flusher.schedule(pm, plane)
    flusher.stop()
    assert [plane.flushed for plane in planes] == [1] * 5
    flusher.stop()  # nothing running: returns at once
