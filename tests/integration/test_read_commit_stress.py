"""Readers and committers over four partitions, through the public
API (PR 28): with the parent's ``read_many_fused`` a cross-partition
read stood in one partition's prepared wait holding another's reader
count, the prepared transaction's commit stood in that partition's
quiesce wait, and after ``read_wait_timeout`` (5 s) the read failed.
Here: no read fails, every value equals the host materializer's at the
read's snapshot, and no wait of the partition manager comes near that
timeout.  Alone on this machine the longest wait is under 1 s; with the
suite's other workers on the same cores a committer that waits for
readers to leave the device has been seen to wait 1.0-1.5 s (readers
share the count, so a writer can be passed), hence a bound of 2.5 s:
half of what the deadlock's waits last.

PR 29's case: the committers' ten keys hold six sets, added to and
removed from, so every update makes the coordinator's one batched read
of the state its downstreams need (``txn_state_read``) beside the
readers' drains and the other committers' multi-partition commits."""

import random
import sys
import threading
import time

import pytest

from antidote_tpu import stats
from antidote_tpu.api import AntidoteTPU
from antidote_tpu.config import Config
from antidote_tpu.crdt import get_type
from antidote_tpu.txn.coordinator import TransactionAborted

CK, SK = "counter_pn", "set_aw"
KEYS = [(f"c{i}", CK) for i in range(300)] + [(f"s{i}", SK)
                                              for i in range(100)]
READERS = WRITERS = 8
READS_EACH, UPDATES_EACH = 120, 130      # 2,000 transactions


def update_of(rng, obj, removes=False):
    if obj[1] == CK:
        return (obj, "increment", rng.randint(1, 99))
    op = rng.choice(["add", "remove"]) if removes else "add"
    return (obj, op, rng.randint(0, 5))


def keys_of(rng, set_keys):
    """A committer's ten keys: as they fall, or ``set_keys`` of them
    sets."""
    if set_keys is None:
        return rng.sample(KEYS, 10)
    return rng.sample(KEYS[300:], set_keys) \
        + rng.sample(KEYS[:300], 10 - set_keys)


@pytest.mark.parametrize("seed,set_keys", [(28, None), (29, 6)])
def test_no_read_fails_or_is_wrong_and_no_wait_nears_the_timeout(
        tmp_path, seed, set_keys):
    db = AntidoteTPU(dc_id="dc1", data_dir=str(tmp_path / "d"),
                     config=Config(n_partitions=4, metrics_port=None,
                                   device_lanes=64))
    pms = db.node.partitions
    rng = random.Random(seed)
    db.update_objects_static(None, [update_of(rng, o) for o in KEYS])
    db.read_objects_static(None, KEYS)      # compiles before the clock
    waits = []
    for pm in pms:
        # as the benchmark's keyspace is twice its value cache: most
        # keys of a read fold on the device, and the read holds counts
        pm._val_cache_cap = 4
        pm._val_cache.clear()
        for name in ("_await_unprepared", "_wait_device_quiesce"):
            def timed(*a, _orig=getattr(pm, name), **kw):
                t0 = time.monotonic()
                try:
                    return _orig(*a, **kw)
                finally:
                    waits.append(time.monotonic() - t0)

            setattr(pm, name, timed)
    reads, errors, aborts = [], [], []
    state_reads = stats.registry.update_state_reads
    batched0 = state_reads.value(path="batched")
    single0 = state_reads.value(path="single")
    # a reader reads at the newest commit clock any committer was
    # answered with (a session handed on): such a snapshot lies above
    # the prepare time of a transaction still committing, which the
    # stable time never does, so these reads meet prepared keys
    newest = [None]
    start = threading.Barrier(READERS + WRITERS)

    def reader(n):
        r = random.Random(seed * 100 + n)
        start.wait()
        for _ in range(READS_EACH):
            objs = r.sample(KEYS, 10)
            try:
                values, clock = db.read_objects_static(newest[0], objs)
            except Exception as e:  # noqa: BLE001 — the finding
                errors.append(("read", repr(e)))
                return
            reads.append((objs, values, clock))
            # think time: with every reader always in flight the serve
            # windows are never idle and no read takes the direct
            # cross-partition path, the one that deadlocked
            time.sleep(r.uniform(0.0, 0.03))

    def writer(n):
        r = random.Random(seed * 1000 + n)
        start.wait()
        clock = None
        for _ in range(UPDATES_EACH):
            updates = [update_of(r, o, removes=set_keys is not None)
                       for o in keys_of(r, set_keys)]
            for _attempt in range(200):
                try:
                    clock = db.update_objects_static(clock, updates)
                    newest[0] = clock
                    break
                except TransactionAborted:
                    aborts.append(n)
                    time.sleep(r.uniform(0.001, 0.01))
                except Exception as e:  # noqa: BLE001 — the finding
                    errors.append(("update", repr(e)))
                    return
            else:
                errors.append(("update", "aborted 200 times"))
                return

    threads = [threading.Thread(target=reader, args=(n,), daemon=True)
               for n in range(READERS)]
    threads += [threading.Thread(target=writer, args=(n,), daemon=True)
                for n in range(WRITERS)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)     # more interleavings a second
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=240)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads), "a client hangs"
    assert errors == []
    assert len(reads) == READERS * READS_EACH
    assert [pm._dev_readers for pm in pms] == [0, 0, 0, 0]
    # every set key of every attempt read its state in the one batch
    assert state_reads.value(path="single") == single0
    if set_keys is not None:
        assert state_reads.value(path="batched") - batched0 == set_keys * (
            WRITERS * UPDATES_EACH + len(aborts))
    assert max(waits, default=0.0) < 2.5, sorted(waits)[-5:]
    # every value of every read against the host materializer's log
    # replay at the snapshot the read returned
    wrong = []
    memo = {}
    for objs, values, clock in reads:
        for (key, tn), value in zip(objs, values):
            at = (key, tuple(sorted(dict(clock).items())))
            if at not in memo:
                pm = db.node.partition_of(key)
                with pm._lock:
                    memo[at] = get_type(tn).value(
                        pm._read_from_log(key, tn, clock))
            if memo[at] != value:
                wrong.append((key, value, memo[at]))
    assert wrong == [], wrong[:5]
    assert sum(len(v) for _o, v, _c in reads) == 10 * len(reads)
    db.close()
