"""Node-failure and network-partition tests — the
multiple_dcs_node_failure_SUITE analogue (reference
test/multidc/multiple_dcs_node_failure_SUITE.erl:85-120: kill nodes,
restart, assert log-recovered state and continued replication) and the
cookie-partition helpers (reference test_utils partition_cluster /
heal_cluster, test/utils/test_utils.erl:239-256).
"""

import time

import pytest

from antidote_tpu.config import Config
from antidote_tpu.interdc.dc import DataCenter

from tests.multidc.conftest import make_cluster


def _upd(dc, key, n=1, clock=None):
    return dc.update_objects_static(
        clock, [((key, "counter_pn", "bkt"), "increment", n)])


def _read(dc, key, clock):
    vals, _ = dc.read_objects_static(clock, [(key, "counter_pn", "bkt")])
    return vals[0]


def _wait(dc, key, want, clock=None, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if _read(dc, key, clock) == want:
            return
        time.sleep(0.01)
    assert _read(dc, key, clock) == want


def test_dc_restart_recovers_state_and_replication(bus, tmp_path):
    """Kill dc1, write at dc2 while it is down, restart dc1 from its
    data dir: recovered local state + gap-repaired remote stream
    (reference failure_test, multiple_dcs_node_failure_SUITE.erl:85-120)."""
    dcs = make_cluster(bus, tmp_path, 3)
    dc1, dc2, dc3 = dcs
    try:
        key = "fail_key"
        ct = _upd(dc1, key, 3)
        for dc in dcs:
            _wait(dc, key, 3, ct)

        # "kill -15" dc1
        dc1.close()

        # dc2 keeps committing while dc1 is down; these frames are lost
        # to dc1 (its subscription is gone)
        ct2 = _upd(dc2, key, 2, clock=None)

        # restart dc1 from the same data dir: meta re-joins known DCs,
        # logs replay, sender watermarks and dependency clocks reseed
        dc1b = DataCenter("dc1", bus, config=dc2.node.config.__class__(
            n_partitions=4, heartbeat_s=0.02, clock_wait_timeout_s=10.0),
            data_dir=str(tmp_path / "dc1"))
        dcs[0] = dc1b
        dc1b.start_bg_processes()

        # pre-kill state recovered from the durable log.  Not instant:
        # the op's dependency VC covers dc2, so it stays (correctly)
        # invisible until dc2's heartbeats re-advance dc1's stable
        # snapshot past it — hence a poll, like the reference's
        # wait_until assertions.
        deadline = time.monotonic() + 10.0
        while _read(dc1b, key, None) < 3:
            assert time.monotonic() < deadline, "recovered state invisible"
            time.sleep(0.01)

        # a fresh dc2 commit triggers the opid gap check at dc1, which
        # repairs the missed range via the log-read RPC
        ct3 = _upd(dc2, key, 1, clock=ct2)
        _wait(dc1b, key, 6, timeout=15.0)

        # and dc1's own new writes still replicate out
        ct4 = _upd(dc1b, key, 1, clock=None)
        for dc in (dc2, dc3):
            _wait(dc, key, 7, timeout=15.0)
    finally:
        for dc in dcs:
            dc.close()


def test_network_partition_and_heal(bus, tmp_path):
    """Cut the dc1<->dc2 link: updates stop flowing but both sides stay
    available; heal: convergence resumes (reference partition_cluster /
    heal_cluster, test/utils/test_utils.erl:239-256)."""
    dcs = make_cluster(bus, tmp_path, 2)
    dc1, dc2 = dcs
    try:
        key = "part_key"
        ct = _upd(dc1, key, 1)
        _wait(dc2, key, 1, ct)

        bus.set_link("dc1", "dc2", False)
        bus.set_link("dc2", "dc1", False)

        _upd(dc1, key, 1)
        # dc2 never observes the partitioned write (ungated read)
        time.sleep(0.2)
        assert _read(dc2, key, None) == 1
        # both sides remain available for local work
        _upd(dc2, "local_key", 5)

        bus.set_link("dc1", "dc2", True)
        bus.set_link("dc2", "dc1", True)

        # after heal, the next frames trigger gap repair and both sides
        # converge
        _upd(dc1, key, 1)
        _wait(dc2, key, 3, timeout=15.0)
        _wait(dc1, "local_key", 5, timeout=15.0)
    finally:
        for dc in dcs:
            dc.close()


def _wait_converged(dcs, merged, objs, types, timeout=30.0):
    """Poll until every replica reads identical values at ``merged``;
    clock-wait timeouts keep polling (a replica may still be
    gap-repairing), so only true divergence — reported per type —
    fails."""
    deadline = time.monotonic() + timeout
    while True:
        views = []
        for dc in dcs:
            try:
                vals, _ = dc.read_objects_static(merged, objs)
            except TimeoutError:
                views = None
                break
            views.append(vals)
        if views is not None and all(v == views[0] for v in views[1:]):
            return views
        assert time.monotonic() < deadline, (
            "replicas did not converge: "
            + ("a replica's clock wait kept timing out"
               if views is None else
               "; ".join(f"{t}: " + "/".join(repr(v[i]) for v in views)
                         for i, t in enumerate(types)
                         if any(v[i] != views[0][i] for v in views))))
        time.sleep(0.05)


@pytest.mark.parametrize("seed", [11, 23, 37])
def test_chaos_all_types_converge(bus, tmp_path, seed):
    """Randomized workload over (almost) every CRDT type across 3 DCs
    with a link flap, a lost-frames window (drop_rx), and a mid-stream
    DC restart: all replicas converge to identical values at the merged
    causal clock — dependency gating, gap repair, recovery, and every
    materializer path exercised at once.  (counter_b is excluded: its
    decrements legitimately abort on rights, covered by its own suite.)
    This harness found the cross-origin dependency-gate deadlock that
    a stamp counted behind a blocked head resolves (interdc/dep.py
    ``_raise_watermarks``; until PR 36 the reference's blocked-head
    rule)."""
    import random

    from antidote_tpu.clocks import vc_max

    rng = random.Random(seed)
    dcs = make_cluster(bus, tmp_path, 3)
    try:
        elems = ["a", "b", "c", "d"]

        def random_update(tname):
            if tname in ("counter_pn", "counter_fat"):
                return ("increment", rng.randint(1, 3))
            if tname in ("set_aw", "set_rw", "set_go"):
                if tname != "set_go" and rng.random() < 0.35:
                    return ("remove", rng.choice(elems))
                return ("add", rng.choice(elems))
            if tname in ("register_lww", "register_mv"):
                return ("assign", rng.choice(elems))
            if tname in ("flag_ew", "flag_dw"):
                return (rng.choice(["enable", "disable"]), ())
            if tname == "map_go":
                return ("update", ((("n", "counter_pn"),
                                    ("increment", 1))))
            if tname == "map_rr":
                if rng.random() < 0.25:
                    return ("remove", ("tags", "set_aw"))
                return ("update", ((("tags", "set_aw"),
                                    ("add", rng.choice(elems)))))
            if tname == "rga":
                return ("add_right", (0, rng.choice(elems)))
            raise AssertionError(tname)

        types = ["counter_pn", "counter_fat", "set_aw", "set_rw",
                 "set_go", "register_lww", "register_mv", "flag_ew",
                 "flag_dw", "map_go", "map_rr", "rga"]
        clocks = [None, None, None]

        def burst(n, causal=True):
            for _ in range(n):
                i = rng.randrange(3)
                tname = rng.choice(types)
                key = (f"chaos_{tname}", tname, "bkt")
                op = random_update(tname)
                clocks[i] = dcs[i].update_objects_static(
                    clocks[i] if causal else None, [(key, *op)])

        burst(40)
        # cut dc1<->dc2: both stay available, but a causal floor that
        # straddles the cut would (correctly) block Clock-SI until the
        # heal — so the partition-window writes carry no floor
        bus.set_link("dc1", "dc2", False)
        burst(20, causal=False)
        bus.set_link("dc1", "dc2", True)   # heal: gap repair refetches
        burst(20)
        # silently drop frames INBOUND to dc2 (lost messages without a
        # link cut: the senders see nothing; only opid gap repair can
        # recover the stream)
        bus.set_drop_rx("dc2", True)
        burst(15, causal=False)
        bus.set_drop_rx("dc2", False)
        burst(15)
        # hard restart dc3 from its data dir mid-workload
        dcs[2].close()
        dcs[2] = DataCenter(
            "dc3", bus,
            config=Config(n_partitions=4, heartbeat_s=0.02,
                          clock_wait_timeout_s=10.0),
            data_dir=str(tmp_path / "dc3"))
        dcs[2].start_bg_processes()
        clocks[2] = None
        burst(40)

        merged = vc_max([c for c in clocks if c is not None])
        objs = [(f"chaos_{t}", t, "bkt") for t in types]
        views = _wait_converged(dcs, merged, objs, types)
        # sanity: the workload actually produced state everywhere
        assert any(v not in (0, [], {}, False, None) for v in views[0])
    finally:
        for dc in dcs:
            dc.close()


def test_chaos_concurrent_writers_converge(bus, tmp_path):
    """Three writer THREADS (one per DC) run causal chains of mixed-type
    updates while the main thread injects a link flap and a lost-frames
    window; afterwards every replica converges at the merged clock.
    Exercises the locking seams the sequential chaos cannot: concurrent
    publish vs device flush/GC quiesce, warm-cache applies under the
    partition lock, and gate processing against live appenders."""
    import random
    import threading

    from antidote_tpu.clocks import vc_max

    dcs = make_cluster(bus, tmp_path, 3)
    try:
        types = ["counter_pn", "set_aw", "set_rw", "flag_dw", "map_rr",
                 "register_mv"]
        elems = ["a", "b", "c"]
        finals = [None, None, None]
        errs = []

        stop_writers = threading.Event()

        def writer(i):
            rng = random.Random(100 + i)
            dc = dcs[i]
            ct = None
            try:
                # run until the injector has finished its windows (a
                # fixed op count races the machine's speed: fast runs
                # finished before the drop window, failing the overlap
                # assertion vacuously)
                while not stop_writers.is_set():
                    t = rng.choice(types)
                    key = (f"cc_{t}", t, "bkt")
                    if t == "counter_pn":
                        op = ("increment", 1)
                    elif t in ("set_aw", "set_rw"):
                        op = (rng.choice(["add", "remove"]),
                              rng.choice(elems))
                    elif t == "flag_dw":
                        op = (rng.choice(["enable", "disable"]), ())
                    elif t == "map_rr":
                        op = ("update", ((("s", "set_aw"),
                                          ("add", rng.choice(elems)))))
                    else:
                        op = ("assign", rng.choice(elems))
                    try:
                        ct = dc.update_objects_static(ct, [(key, *op)])
                        # record every successful commit: the merged
                        # convergence clock must cover this DC's tail
                        # even if a LATER op times out
                        finals[i] = ct
                    except TimeoutError:
                        # a causal floor straddling an injected fault
                        # window blocks (correct Clock-SI); shed the
                        # floor and continue like a reconnecting client
                        ct = None
            except Exception as e:  # noqa: BLE001 — surfaced below
                errs.append((i, e))

        threads = [threading.Thread(target=writer, args=(i,),
                                    daemon=True)  # a wedged writer must
                   for i in range(3)]             # not hang the process
        for t in threads:
            t.start()
        # fault injection against the live writers; assert the windows
        # actually overlapped live writes (otherwise the test passes
        # vacuously on a fast machine)
        time.sleep(0.3)
        assert any(t.is_alive() for t in threads), \
            "writers finished before fault injection began"
        bus.set_link("dc1", "dc2", False)
        time.sleep(0.4)
        bus.set_link("dc1", "dc2", True)
        time.sleep(0.2)
        bus.set_drop_rx("dc3", True)
        time.sleep(0.4)
        overlapped = any(t.is_alive() for t in threads)
        bus.set_drop_rx("dc3", False)
        stop_writers.set()
        assert overlapped, \
            "writers finished before the drop window ended"
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive(), "writer wedged"
        assert not errs, errs

        merged = vc_max([c for c in finals if c is not None])
        objs = [(f"cc_{t}", t, "bkt") for t in types]
        _wait_converged(dcs, merged, objs, types)
    finally:
        for dc in dcs:
            dc.close()
