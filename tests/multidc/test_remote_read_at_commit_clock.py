"""A read at a peer DC at a commit clock of the origin sees that commit
(ISSUE 36, PERF.md §7.2).

An origin partition's stream arrives in LOG order, and two transactions
prepared together log their commits in either order.  Until PR 36 the
receiving gate raised the origin's watermark to every applied
transaction's commit time, so the later commit, applied first, let a
read at the earlier commit's clock through before that commit had
arrived: ``remote_reads_wrong`` in one read of seven under load.  The
rule now: the watermark is the origin's own stamp (its min-prepared
time), bounded by what the receiver holds unapplied.

(a) that very interleaving, driven by hand with no thread racing, over
the host walk and the device ring and over both transports; (b) the
probe that found it, as a seeded stress."""

import random
import threading
import time

import pytest

from antidote_tpu.clocks import VC
from antidote_tpu.config import Config
from antidote_tpu.interdc import InProcBus
from antidote_tpu.interdc.dc import DataCenter, connect_dcs
from antidote_tpu.interdc.tcp import TcpTransport
from antidote_tpu.txn.coordinator import TransactionAborted


class Held:
    """Holds the frames one DC publishes until released (a slow link:
    nothing is lost, so the receivers see no gap)."""

    def __init__(self, bus, origin):
        self.origin, self.frames, self.holding = origin, [], False
        self._send = bus.publish
        bus.publish = self._publish

    def _publish(self, origin, data, **kw):
        if self.holding and origin == self.origin:
            self.frames.append((data, kw))
        else:
            self._send(origin, data, **kw)

    def release(self):
        self.holding = False
        for data, kw in self.frames:
            self._send(self.origin, data, **kw)
        self.frames = []


def _until(cond, *pump, timeout=10.0):
    """Pump the given DCs (deterministic delivery: no background
    thread runs) until ``cond()`` holds."""
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, "never happened"
        for dc in pump:
            dc.pump()
        time.sleep(0.001)


def _commit_by_hand(dc, txid, key, snapshot=None):
    """Stage and prepare one counter increment on dc's only partition;
    returns (prepare time, commit) for the caller to order; commit()
    returns the commit record's opid in dc's stream."""
    pm = dc.node.partitions[0]
    pm.stage_update(txid, key, "counter_pn", 1)
    pt = pm.prepare(txid, VC())

    def commit():
        pm.commit(txid, pt, VC(snapshot or {}))
        dc.senders[0].flush_ship()  # close the frame now, with its stamp
        return dc.senders[0].last_sent_opid
    return pt, commit


@pytest.mark.parametrize("transport", ["inproc", "tcp"])
@pytest.mark.parametrize("walk", ["host", "ring"])
def test_watermark_is_the_origins_stamp_not_an_applied_commit(
        tmp_path, walk, transport):
    shared = InProcBus()
    dcs, held = [], {}
    for name in ("dc1", "dc2", "dc3"):
        bus = shared if transport == "inproc" else TcpTransport()
        dcs.append(DataCenter(
            name, bus, data_dir=str(tmp_path / name),
            config=Config(
                n_partitions=1, device_store=False,
                clock_wait_timeout_s=20.0, gate_coalesce_us=0,
                # a frame closes when _commit_by_hand says so, never
                # on the ship worker's own window (its stamp would be
                # drawn with the committer still in the prepared table)
                interdc_ship_us=60_000_000,
                gate_batch_threshold=10**9 if walk == "host" else 0)))
        held[name] = Held(bus, name)
    dc1, dc2, dc3 = dcs
    try:
        connect_dcs(dcs)  # no start_bg_processes: every delivery is a pump
        # the newest opid of origin's stream in dc's log
        applied = lambda dc, origin: \
            dc.node.partitions[0].log.op_counters.get(origin, 0)
        seen = lambda dc, origin: dc.node.stable_vc().get_dc(origin)

        # ---- the log-order interleaving.  A and B are prepared
        # together; B, with the LARGER commit time, logs its commit
        # first, ships, and is applied at dc2 while A's frame is still
        # on the wire.
        ct_a, commit_a = _commit_by_hand(dc1, ("dc1", 1), "kA")
        ct_b, commit_b = _commit_by_hand(dc1, ("dc1", 2), "kB")
        assert ct_a < ct_b
        op_b = commit_b()
        _until(lambda: applied(dc2, "dc1") == op_b, dc2)
        held["dc1"].holding = True
        op_a = commit_a()
        assert held["dc1"].frames
        dc2.pump()
        time.sleep(0.01)  # outlive Node.stable_vc()'s 2 ms cache
        # dc2 has applied ct_b and may NOT say "dc1 is applied up to
        # ct_a": the stamp that rode with B was dc1's min-prepared, A's
        # prepare time
        assert seen(dc2, "dc1") < ct_a, (seen(dc2, "dc1"), ct_a, ct_b)
        got = []
        reader = threading.Thread(target=lambda: got.append(
            dc2.read_objects_static(VC({"dc1": ct_a}),
                                    [("kA", "counter_pn")])[0]))
        reader.start()
        reader.join(0.3)
        assert reader.is_alive() and not got, \
            "a read at A's commit clock went through before A arrived"
        held["dc1"].release()
        reader.join(10.0)
        assert got == [[1]]
        vals, _ = dc2.read_objects_static(
            VC({"dc1": ct_b}), [("kA", "counter_pn"), ("kB", "counter_pn")])
        assert vals == [1, 1]
        assert seen(dc2, "dc1") >= ct_b

        # ---- two origins whose heads wait on each other, each with
        # its stamp queued BEHIND the blocked transaction: the stamps
        # count at once, bounded by what is queued, and unknot them.
        _until(lambda: applied(dc3, "dc1") == op_a, dc3)
        dep = dc1.node.clock.now_us()  # above every watermark dc3 holds
        held["dc2"].holding = True
        ct_c, commit_c = _commit_by_hand(dc1, ("dc1", 3), "kC", {"dc2": dep})
        ct_d, commit_d = _commit_by_hand(dc2, ("dc2", 1), "kD", {"dc1": dep})
        op_c, op_d = commit_c(), commit_d()
        _until(lambda: dc3.dep_gates[0].pending() == 1, dc3)
        time.sleep(0.01)
        # C waits for dc2; its stream's stamp, behind it, still counts:
        # dc1 stands just below its one queued commit
        assert applied(dc3, "dc1") == op_a
        assert seen(dc3, "dc1") == ct_c - 1 >= dep
        held["dc2"].release()
        _until(lambda: applied(dc3, "dc1") == op_c
               and applied(dc3, "dc2") == op_d, dc3)
        vals, _ = dc3.read_objects_static(
            VC({"dc1": ct_c, "dc2": ct_d}),
            [("kC", "counter_pn"), ("kD", "counter_pn")])
        assert vals == [1, 1]
    finally:
        for h in held.values():
            h.holding = False
        for dc in dcs:
            dc.close()
            if transport == "tcp":
                dc.bus.close()


def test_probers_read_their_own_commits_at_the_peer(tmp_path):
    """The probe that found the fault, seeded: 8 writers at dc1 send
    10-key increments over 400,000 keys with session clocks; 4 probers
    increment 3 keys only they write plus 5 shared ones in ONE
    transaction at dc1 and at once read their own keys at dc2 at the
    returned clock.  Every value is the prober's own count.  Before
    PR 36: 12-15 % of them were the value before that very commit."""
    bus = InProcBus()
    dcs = [DataCenter(f"dc{i + 1}", bus, data_dir=str(tmp_path / f"dc{i + 1}"),
                      # no checkpoints: a cut stops the partition's
                      # committers, and a peer can read nothing newer
                      # than dc1's oldest prepared transaction — slow
                      # under six busy test workers, and not the point
                      config=Config(n_partitions=1, device_store=False,
                                    heartbeat_s=0.02, ckpt=False,
                                    clock_wait_timeout_s=30.0))
           for i in range(2)]
    connect_dcs(dcs)
    for dc in dcs:
        dc.start_bg_processes()
    dc1, dc2 = dcs
    stop = threading.Event()
    n_keys = 400_000
    checked, wrong, errors = [0] * 4, [0] * 4, []

    def shared(rng, n):
        return [((f"k{rng.randrange(n_keys)}", "counter_pn"), "increment", 1)
                for _ in range(n)]

    def writer(i):
        rng, clock = random.Random(3600 + i), None
        while not stop.is_set():
            try:
                clock = dc1.update_objects_static(clock, shared(rng, 10))
            except TransactionAborted:
                pass  # two writers met on a shared key: certification

    def prober(i):
        rng, count = random.Random(3690 + i), 0
        own = [(f"own{i}_{j}", "counter_pn") for j in range(3)]
        while not stop.is_set():
            try:
                ct = dc1.update_objects_static(
                    None,
                    [(k, "increment", 1) for k in own] + shared(rng, 5))
            except TransactionAborted:
                continue
            count += 1
            vals, _ = dc2.read_objects_static(ct, own)
            checked[i] += len(vals)
            wrong[i] += sum(v != count for v in vals)

    def guarded(fn, i):
        try:
            fn(i)
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(repr(e))
            stop.set()

    threads = [threading.Thread(target=guarded, args=(fn, i))
               for fn, n in ((writer, 8), (prober, 4)) for i in range(n)]
    try:
        t0 = time.monotonic()
        for t in threads:
            t.start()
        while not stop.is_set() and time.monotonic() - t0 < 120.0 and (
                sum(checked) < 2100 or time.monotonic() - t0 < 3.0):
            time.sleep(0.05)
    finally:
        stop.set()
        for t in threads:
            t.join(40.0)
        for dc in dcs:
            dc.close()
    assert not errors, errors
    assert sum(checked) >= 2000, checked
    assert sum(wrong) == 0, (sum(wrong), sum(checked))
