"""Batched shipping plane (ISSUE 6): coalescing/budget behavior, the
async sender's ordering guarantees (including the pre-ISSUE-6
concurrent-append ordering race, as a regression test), heartbeat
piggybacking, backpressure, and the SHIP_* counters."""

import threading
import time

import pytest

from antidote_tpu import stats
from antidote_tpu.clocks import VC
from antidote_tpu.config import Config
from antidote_tpu.interdc.sender import InterDcLogSender, est_txn_bytes
from antidote_tpu.interdc.wire import InterDcBatch, InterDcTxn, frame_from_bin
from antidote_tpu.oplog.records import OpId, commit_record, update_record


class Capture:
    """Transport stub recording publish order; optionally slow or
    gated (backpressure tests)."""

    def __init__(self, delay=0.0, gate=None):
        self.frames = []
        self.delay = delay
        self.gate = gate
        self._lock = threading.Lock()

    def publish(self, origin, data):
        if self.gate is not None:
            self.gate.wait()
        if self.delay:
            time.sleep(self.delay)
        with self._lock:
            self.frames.append(bytes(data))

    def decoded(self):
        with self._lock:
            return [frame_from_bin(d) for d in self.frames]


def cfg(**kw):
    kw.setdefault("interdc_ship", True)
    return Config(**kw)


def feed_txn(sender, i, opid, nup=1, dc="dc1"):
    """Append one txn's records (nup updates + commit); returns the new
    opid watermark."""
    txid = (dc, 1000 + i)
    for _ in range(nup):
        opid += 1
        sender.on_append(update_record(
            OpId(dc, opid), txid, f"k{i}", "counter_pn", ("increment", 1)))
    opid += 1
    sender.on_append(commit_record(
        OpId(dc, opid), txid, dc, 10_000 + i, VC({dc: 9_000 + i})))
    return opid


def all_txns(frames):
    """Flatten decoded frames into the delivered txn sequence."""
    out = []
    for f in frames:
        if isinstance(f, InterDcBatch):
            out.extend(f.txns())
        elif not f.is_ping():
            out.append(f)
    return out


class TestShipCoalescing:
    def test_burst_ships_as_few_batch_frames(self):
        cap = Capture()
        s = InterDcLogSender("dc1", 0, cap, config=cfg(
            interdc_ship_txns=8, interdc_ship_us=500_000))
        opid = 0
        for i in range(20):
            opid = feed_txn(s, i, opid)
        s.flush_ship()
        frames = cap.decoded()
        assert all(isinstance(f, InterDcBatch) for f in frames)
        assert len(frames) <= 4  # 20 txns / 8-txn budget, window held
        assert all(len(f.txns()) <= 8 for f in frames)
        txns = all_txns(frames)
        assert len(txns) == 20
        # contiguous watermarks across the whole stream
        prev = 0
        for t in txns:
            assert t.prev_log_opid == prev
            prev = t.last_opid()
        s.close()

    def test_byte_budget_closes_frames_early(self):
        cap = Capture()
        s = InterDcLogSender("dc1", 0, cap, config=cfg(
            interdc_ship_txns=1000, interdc_ship_us=500_000,
            interdc_ship_bytes=1))  # every txn overflows the budget
        opid = 0
        for i in range(6):
            opid = feed_txn(s, i, opid)
        s.flush_ship()
        frames = cap.decoded()
        assert len(frames) == 6  # budget forces one txn per frame
        assert len(all_txns(frames)) == 6
        s.close()

    def test_window_expiry_ships_without_budget(self):
        cap = Capture()
        s = InterDcLogSender("dc1", 0, cap, config=cfg(
            interdc_ship_txns=1000, interdc_ship_us=2_000))
        opid = feed_txn(s, 0, 0)
        deadline = time.monotonic() + 2.0
        while not cap.frames and time.monotonic() < deadline:
            time.sleep(0.005)
        assert cap.frames, "window expiry never shipped the lone txn"
        (f,) = cap.decoded()
        assert isinstance(f, InterDcBatch) and len(f.txns()) == 1
        assert f.last_opid() == opid
        s.close()

    def test_disabled_sender_stages_nothing(self):
        cap = Capture()
        s = InterDcLogSender("dc1", 0, cap, enabled=False, config=cfg())
        opid = feed_txn(s, 0, 0)
        assert s.pending_ship() == 0 and not cap.frames
        # the watermark still advanced (recovery contract)
        assert s.last_sent_opid == opid
        s.close()

    def test_ship_false_keeps_legacy_per_txn_frames(self):
        cap = Capture()
        s = InterDcLogSender("dc1", 0, cap,
                             config=cfg(interdc_ship=False))
        opid = 0
        for i in range(5):
            opid = feed_txn(s, i, opid)
        frames = cap.decoded()
        assert len(frames) == 5
        assert all(isinstance(f, InterDcTxn) for f in frames)
        s.close()

    def test_unpackable_txn_falls_back_in_order(self):
        """A hand-built txn outside the batch contract ships as a
        legacy frame, with any open batch closed ahead of it."""
        cap = Capture()
        s = InterDcLogSender("dc1", 0, cap, config=cfg(
            interdc_ship_txns=64, interdc_ship_us=500_000))
        opid = feed_txn(s, 0, 0)
        # op id beyond int64: unpackable by contract
        txid = ("dc1", 2000)
        s.on_append(update_record(OpId("dc1", 2 ** 70), txid, "k",
                                  "counter_pn", 1))
        s.on_append(commit_record(OpId("dc1", 2 ** 70 + 1), txid, "dc1",
                                  77, VC({"dc1": 70})))
        s.flush_ship()
        frames = cap.decoded()
        assert isinstance(frames[0], InterDcBatch)
        assert frames[0].last_opid() == opid
        assert isinstance(frames[1], InterDcTxn)
        assert frames[1].prev_log_opid == opid
        s.close()


class TestOrdering:
    def test_concurrent_appends_publish_in_watermark_order(self):
        """The pre-ISSUE-6 race: on_append advanced last_sent_opid
        under the lock but published after releasing it, so two
        committing threads could emit frames out of opid order.  Both
        paths must now publish per-stream FIFO under concurrency."""
        for ship in (False, True):
            cap = Capture()
            s = InterDcLogSender("dc1", 0, cap, config=cfg(
                interdc_ship=ship, interdc_ship_txns=4,
                interdc_ship_us=0))
            n_threads, per = 8, 25
            lock = threading.Lock()
            opid_box = [0]

            def committer(t):
                for i in range(per):
                    # record construction serialized (the log assigns
                    # dense opids under the partition lock in prod)
                    with lock:
                        opid_box[0] = feed_txn(
                            s, t * 1000 + i, opid_box[0])

            threads = [threading.Thread(target=committer, args=(t,))
                       for t in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            s.flush_ship()
            txns = all_txns(cap.decoded())
            assert len(txns) == n_threads * per, ship
            prev = 0
            for t in txns:
                assert t.prev_log_opid == prev, \
                    f"out-of-order publish (ship={ship})"
                prev = t.last_opid()
            s.close()

    def test_backpressure_bounds_the_staging_buffer(self):
        gate = threading.Event()
        cap = Capture(gate=gate)
        s = InterDcLogSender("dc1", 0, cap, config=cfg(
            interdc_ship_txns=2, interdc_ship_us=0))
        cap_limit = 2 * 4  # ship_txns * SHIP_BACKPRESSURE_FACTOR
        done = threading.Event()

        def producer():
            opid = 0
            for i in range(cap_limit + 6):
                opid = feed_txn(s, i, opid)
            done.set()

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        # the producer must block once the buffer + in-flight frame
        # absorb the cap; give the worker time to wedge on the gate
        time.sleep(0.3)
        assert not done.is_set(), "producer never felt backpressure"
        with s._lock:
            assert len(s._buf) <= cap_limit
        gate.set()
        t.join(timeout=10)
        assert done.is_set()
        s.flush_ship(timeout=5)
        assert len(all_txns(cap.decoded())) == cap_limit + 6
        s.close()


class TestPingPiggyback:
    def test_quiet_stream_pays_standalone_ping(self):
        cap = Capture()
        s = InterDcLogSender("dc1", 0, cap, config=cfg())
        s.ping(123)
        s.flush_ship()
        (f,) = cap.decoded()
        assert isinstance(f, InterDcTxn) and f.is_ping()
        assert f.timestamp == 123
        s.close()

    def test_busy_stream_piggybacks_ping_on_batch(self):
        cap = Capture()
        s = InterDcLogSender("dc1", 0, cap, config=cfg(
            interdc_ship_us=500_000, interdc_ship_txns=64))
        before = stats.registry.ship_piggybacked_pings.value()
        opid = feed_txn(s, 0, 0)
        s.ping(456)
        s.ping(789)  # later stamp supersedes
        assert not cap.frames  # still coalescing — nothing standalone
        s.flush_ship()
        (f,) = cap.decoded()
        assert isinstance(f, InterDcBatch)
        assert f.ping_ts == 789
        ping = f.ping_txn()
        assert ping.prev_log_opid == f.last_opid() == opid
        assert stats.registry.ship_piggybacked_pings.value() == before + 1
        s.close()

    def test_frame_stamp_does_not_overtake_what_is_still_staged(self):
        """A stamp says "all that commits below me is on the wire
        ahead of me".  When a full budget closes a frame with txns
        still staged, they ship BEHIND it: the frame's stamp — the
        ticker's pending one or a fresh min-prepared — is lowered to
        the smallest commit time left staged (commit times are NOT in
        stream order: feed them descending)."""
        cap = Capture()
        s = InterDcLogSender("dc1", 0, cap, min_prepared=lambda: 99_000,
                             config=cfg(interdc_ship_us=500_000))
        opid = 0
        for i in (5, 4, 3, 2, 1):  # commit times 10_005 .. 10_001
            opid = feed_txn(s, i, opid)
        s.ping(50_000)
        assert not cap.frames  # all five staged, the window still open
        s.ship_txns = 2        # ...and now a budget of two a frame
        s.flush_ship()
        frames = cap.decoded()
        assert [len(f.txns()) for f in frames] == [2, 2, 1]
        # each frame's stamp covers nothing staged behind it...
        assert [f.ping_ts for f in frames] == [10_001, 10_001, 99_000]
        # ...and the last, with nothing behind, is the fresh reading
        stamps_ok = all(
            f.ping_ts <= t.timestamp
            for n, f in enumerate(frames) for later in frames[n + 1:]
            for t in later.txns())
        assert stamps_ok
        s.close()

    def test_lone_txn_on_a_quiet_stream_carries_a_stamp_above_it(self):
        """At on_append the committer is still in the prepared table
        (commit() appends, publishes, then pops), so a reading taken
        at staging lies at or below its own commit time and the peer
        would wait for the ticker's heartbeat (1 s by default) to read
        at the commit clock.  The stamp is drawn when the frame
        CLOSES, ship_us later, when the pop has happened."""
        cap = Capture()
        prepared = [10_000]  # the committer's own entry: its commit time

        def min_prepared():
            return prepared[0] if prepared else 20_000  # else the clock

        s = InterDcLogSender("dc1", 0, cap, min_prepared=min_prepared,
                             config=cfg(interdc_ship_us=20_000))
        feed_txn(s, 0, 0)       # commit time 10_000, staged
        prepared.clear()        # ...and the committer pops
        deadline = time.monotonic() + 2.0
        while not cap.frames and time.monotonic() < deadline:
            time.sleep(0.002)
        (f,) = cap.decoded()    # no ticker ran: the frame's own stamp
        assert isinstance(f, InterDcBatch) and len(f.txns()) == 1
        assert f.ping_ts == 20_000 > f.txns()[0].timestamp
        s.close()

    def test_ping_not_gated_on_enabled(self):
        cap = Capture()
        s = InterDcLogSender("dc1", 0, cap, enabled=False, config=cfg())
        s.ping(5)
        s.flush_ship()
        assert len(cap.frames) == 1
        s.close()


class TestShipMetrics:
    def test_counters_and_gauges_track_the_economy(self):
        reg = stats.registry
        f0 = reg.ship_frames.value(kind="batch")
        t0 = reg.ship_txns.value()
        b0 = reg.ship_bytes.value()
        cap = Capture()
        s = InterDcLogSender("dc1", 0, cap, config=cfg(
            interdc_ship_txns=8, interdc_ship_us=500_000))
        opid = 0
        for i in range(16):
            opid = feed_txn(s, i, opid)
        s.flush_ship()
        s.close()
        frames = reg.ship_frames.value(kind="batch") - f0
        assert frames == len(cap.frames) >= 2
        assert reg.ship_txns.value() - t0 == 16
        assert reg.ship_bytes.value() - b0 == \
            sum(len(d) for d in cap.frames)
        assert reg.ship_txns_per_frame.value() > 1
        assert reg.ship_bytes_per_txn.value() > 0

    def test_est_txn_bytes_tracks_payload_size(self):
        small = InterDcTxn.from_ops("dc1", 0, 0, [
            commit_record(OpId("dc1", 1), "t", "dc1", 1, VC({"dc1": 1}))])
        big = InterDcTxn.from_ops("dc1", 0, 0, [
            update_record(OpId("dc1", 1), "t", "k" * 500, "set_aw",
                          ("add", tuple(("e" * 40, ("dc1", i), ())
                                        for i in range(20)))),
            commit_record(OpId("dc1", 2), "t", "dc1", 1, VC({"dc1": 1}))])
        assert est_txn_bytes(big) > est_txn_bytes(small) + 500


class TestShipThroughDataCenter:
    """End-to-end: two DCs on the in-proc bus with the ship plane on —
    batch frames actually flow and replicate values (the multidc suite
    covers semantics; this pins that the DC assembly routes them)."""

    def test_counter_replicates_over_batch_frames(self, tmp_path):
        from antidote_tpu.interdc import InProcBus
        from antidote_tpu.interdc.dc import DataCenter, connect_dcs

        bus = InProcBus()
        dcs = []
        before = stats.registry.ship_frames.value(kind="batch")
        for i in range(2):
            c = Config(n_partitions=2, heartbeat_s=0.02,
                       clock_wait_timeout_s=10.0, interdc_ship=True)
            dcs.append(DataCenter(f"dc{i + 1}", bus, config=c,
                                  data_dir=str(tmp_path / f"dc{i + 1}")))
        try:
            connect_dcs(dcs)
            for dc in dcs:
                dc.start_bg_processes()
            dc1, dc2 = dcs
            ct = None
            for _ in range(10):
                ct = dc1.update_objects_static(
                    ct, [(("ship_k", "counter_pn", "b"), "increment", 1)])
            vals, _ = dc2.read_objects_static(
                ct, [("ship_k", "counter_pn", "b")])
            assert vals[0] == 10
            assert stats.registry.ship_frames.value(
                kind="batch") > before
        finally:
            for dc in dcs:
                dc.close()
