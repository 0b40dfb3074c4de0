"""What a checkpoint holds, and for how long (ISSUE 32).

The contract under test: a checkpoint stops its partition for two
holds of the partition lock, both O(dirty keys) — the cut, which
captures the dirty keys' states and frontiers, and the adopt, which
installs the seeds that changed.  The fold of the captured states runs
between them with the lock released, holding the captures' reader
counts only until the device values are on the host.  Every seed is the
key's state AT the cut: what commits after the hold is in the suffix
and in no seed, and recovery from seeds + suffix is exact.
"""

from __future__ import annotations

import threading

import pytest

from antidote_tpu.clocks import VC
from antidote_tpu.crdt import DownstreamCtx, get_type
from antidote_tpu.mat.materializer import materialize_eager
from antidote_tpu.obs.spans import tracer
from antidote_tpu.txn.manager import read_requests
from antidote_tpu.txn.node import Node
from tests.unit.test_checkpoint import _commit, _mk_cfg

#: the thread every held or failing checkpoint of this file runs on:
#: the patched folds act on it alone, so the test's own reads and
#: commits run the real ones
CKPT = "ckpt"


def _node(tmp_path, **kw):
    """One partition, no watermark checkpoints (every checkpoint below
    is the test's own) and the whole log kept."""
    kw.setdefault("device_store", True)
    cfg = _mk_cfg(tmp_path, ckpt=True, n_partitions=1, ckpt_ops=1 << 30,
                  ckpt_bytes=1 << 40, ckpt_truncate=False, **kw)
    return cfg, Node(dc_id="dc1", config=cfg)


def _in_thread(fn, name=None):
    """Run ``fn`` on a thread of its own; its result or exception is in
    the returned box once the thread is joined."""
    box = {}

    def run():
        try:
            box["result"] = fn()
        except BaseException as e:  # noqa: BLE001 — handed to the test
            box["error"] = e

    t = threading.Thread(target=run, name=name, daemon=True)
    t.start()
    return t, box


def _done(thread, timeout=10.0):
    thread.join(timeout)
    return not thread.is_alive()


def _finishes(fn, what, timeout=5.0):
    t, box = _in_thread(fn)
    assert _done(t, timeout) and "error" not in box, \
        f"{what} stood behind the checkpoint: {box}"
    return box.get("result")


class _History:
    """Certified single-writer history of one type, every effect
    generated from the state the partition reads, with the plain fold
    of the same effects kept beside it: the host-replay reference."""

    def __init__(self, node, type_name, n_keys=3):
        self.node, self.pm = node, node.partitions[0]
        self.tn = type_name
        self.keys = [f"{type_name}_{i}" for i in range(n_keys)]
        self.effects = {k: [] for k in self.keys}
        self.txids = []
        self._n = 0

    def write(self, keys, tag):
        cls = get_type(self.tn)
        ctx = DownstreamCtx(mint=self.node.mint_dot)
        made = []
        for key in keys:
            if self.tn == "counter_pn":
                eff = 7
            else:
                eff = cls.downstream(("add", f"{tag}{self._n}"),
                                     self.state(key), ctx)
            self._n += 1
            txid_n = 32_000_000 + self._n
            _finishes(lambda e=eff, k=key, n=txid_n: _commit(
                self.node, n, [(k, self.tn, e)], certify=True),
                f"a commit of {key}")
            self.effects[key].append(eff)
            made.append((self.node.dc_id, txid_n))
        self.txids.extend(made)
        return made

    def state(self, key, pm=None):
        return (pm or self.pm).read(key, self.tn, None, exact_state=True)

    def states(self, pm=None):
        return {k: self.state(k, pm) for k in self.keys}

    def reference(self):
        bottom = get_type(self.tn).new
        return {k: materialize_eager(self.tn, bottom(), effs)
                for k, effs in self.effects.items()}


def _patch_fold(monkeypatch, fetch=None, post=None):
    """Call ``fetch()`` before the device half and ``post()`` before the
    host half of every flat plane's fold made on the CKPT thread
    (``_many_split`` is where a plane makes both halves)."""
    from antidote_tpu.mat import device_plane as dp

    def on_ckpt():
        return threading.current_thread().name == CKPT

    for cls in (dp.CounterPlane, dp.OrsetPlane):
        real = cls._many_split

        def split(self, *a, _real=real):
            (fn, args), real_post = _real(self, *a)

            def fn2(*args2):
                if fetch is not None and on_ckpt():
                    fetch()
                return fn(*args2)

            def post2(out):
                if post is not None and on_ckpt():
                    post()
                return real_post(out)

            return (fn2, args), post2

        monkeypatch.setattr(cls, "_many_split", split)


class _Gate:
    """A point the CKPT thread stops at, once, until the test lets it
    go; ``readers`` is the partition's reader count as it got there."""

    def __init__(self, pm):
        self.pm = pm
        self.entered, self.release = threading.Event(), threading.Event()
        self.readers = None

    def __call__(self):
        if self.entered.is_set() \
                or threading.current_thread().name != CKPT:
            return
        self.readers = self.pm._dev_readers
        self.entered.set()
        assert self.release.wait(30.0), "the test never let it go"

    def start_checkpoint(self):
        thread, box = _in_thread(self.pm.checkpoint_now, name=CKPT)
        assert self.entered.wait(30.0), \
            f"the checkpoint never reached the gate: {box}"
        return thread, box


def _note_reader_waits(monkeypatch, pm):
    """An event set when a thread enters ``pm._wait_device_quiesce``
    with a reader count held, i.e. when it is about to sleep there."""
    waiting = threading.Event()
    real = pm._wait_device_quiesce

    def quiesce():
        if pm._dev_readers:
            waiting.set()
        real()

    monkeypatch.setattr(pm, "_wait_device_quiesce", quiesce)
    return waiting


# ------------------------------------------------ (a) the lock's scope


def test_fold_holds_a_reader_count_and_no_lock(tmp_path, monkeypatch):
    """With the device half of the checkpoint's fold held open — the
    reader counts taken, the lock released — a stage_group, a prepare
    and a read on the partition finish; a device-route commit waits
    for the count (its publish donates the buffers the fold reads) and
    finishes once it is given back."""
    _cfg, node = _node(tmp_path)
    pm = node.partitions[0]
    ctr, aw = _History(node, "counter_pn"), _History(node, "set_aw")
    for r in range(2):
        ctr.write(ctr.keys, f"r{r}")
        aw.write(aw.keys, f"r{r}")
    gate = _Gate(pm)
    _patch_fold(monkeypatch, fetch=gate)
    thread, box = gate.start_checkpoint()
    waiting = _note_reader_waits(monkeypatch, pm)
    try:
        assert gate.readers == 2, "one count a captured type plane"
        key = ctr.keys[0]
        before = ctr.state(key)
        txid = ("dc1", 424242)
        svc = VC({"dc1": node.clock.now_us()})
        _finishes(lambda: pm.stage_group(txid, [(key, "counter_pn", 5)]),
                  "a stage_group")
        _finishes(lambda: pm.prepare(txid, svc), "a prepare")
        items = [(k, "counter_pn") for k in ctr.keys[1:]] \
            + [(k, "set_aw") for k in aw.keys]
        pm._val_cache.clear()  # the read goes to the planes
        got = _finishes(lambda: read_requests([(pm, items, None)]),
                        "a read's capture")
        assert set(got[0]) == set(items)
        committer, cbox = _in_thread(lambda: pm.commit(
            txid, node.clock.now_us(), svc))
        assert waiting.wait(5.0), \
            f"the commit never reached its wait for readers: {cbox}"
        assert committer.is_alive() and thread.is_alive()
    finally:
        gate.release.set()
    assert _done(committer) and "error" not in cbox, cbox
    assert _done(thread) and box.get("result") is not None, box
    assert ctr.state(key) == before + 5
    assert box["result"]["keys"][key][1] == before, \
        "the commit that waited for the fold is in its seed"
    assert pm._dev_readers == 0 and not pm._ckpt_inflight
    node.close()


def test_commit_record_is_not_in_the_log_across_its_wait_for_readers(
        tmp_path, monkeypatch):
    """A commit waits for device readers with the partition lock
    released — since ISSUE 32 a checkpoint's fold is such a reader —
    and what takes the lock meanwhile must not find the commit record
    in the log before its effects are in the store: a cut taken then
    claims the record and seeds without the effect.  So the wait comes
    before the append."""
    _cfg, node = _node(tmp_path)
    pm = node.partitions[0]
    ctr = _History(node, "counter_pn")
    ctr.write(ctr.keys, "x")
    key = ctr.keys[0]
    before = ctr.state(key)
    txid = ("dc1", 434343)
    svc = VC({"dc1": node.clock.now_us()})
    pm.stage_group(txid, [(key, "counter_pn", 5)])
    pm.prepare(txid, svc)
    waiting = _note_reader_waits(monkeypatch, pm)
    with pm._lock:
        pm._dev_readers += 1  # a capture's count, as a reader takes it
        end = pm.log.log.end_offset()
    committer, cbox = _in_thread(lambda: pm.commit(
        txid, node.clock.now_us(), svc))
    try:
        assert waiting.wait(5.0), f"the commit never waited: {cbox}"
        with pm._lock:
            assert pm.log.log.end_offset() == end, \
                "the commit record is in the log, its effects are not"
            assert committer.is_alive()
    finally:
        with pm._lock:
            pm._dev_readers -= 1
            pm._lock.notify_all()
    assert _done(committer) and "error" not in cbox, cbox
    assert pm.log.log.end_offset() > end
    assert ctr.state(key) == before + 5
    node.close()


def test_count_is_back_before_the_decode(tmp_path, monkeypatch):
    """The flat planes' folds give their reader counts back when the
    device values are on the host: the decode runs with none held."""
    _cfg, node = _node(tmp_path)
    pm = node.partitions[0]
    ctr, aw = _History(node, "counter_pn"), _History(node, "set_aw")
    ctr.write(ctr.keys, "x")
    aw.write(aw.keys, "x")
    gate = _Gate(pm)
    _patch_fold(monkeypatch, post=gate)
    thread, box = gate.start_checkpoint()
    gate.release.set()
    assert _done(thread) and box.get("result") is not None, box
    assert gate.readers == 0
    node.close()


def test_a_second_caller_during_the_fold_reuses_the_last_document(
        tmp_path, monkeypatch):
    """The in-flight guard covers the fold now that it runs outside the
    lock: a second checkpoint_now returns the adopted document at once
    and leaves the dirty set to the one in flight."""
    _cfg, node = _node(tmp_path)
    pm = node.partitions[0]
    ctr = _History(node, "counter_pn")
    ctr.write(ctr.keys, "x")
    first = pm.checkpoint_now()
    ctr.write(ctr.keys[:1], "y")
    gate = _Gate(pm)
    _patch_fold(monkeypatch, post=gate)
    thread, box = gate.start_checkpoint()
    try:
        assert _finishes(pm.checkpoint_now, "a second checkpoint") is first
    finally:
        gate.release.set()
    assert _done(thread) and box["result"] is pm.log.ckpt_doc, box
    assert box["result"]["cut_offset"] > first["cut_offset"]
    node.close()


# ------------------------------------------------------- (b) exactness


@pytest.mark.parametrize("type_name", ["counter_pn", "set_aw", "set_rw"])
def test_seeds_are_the_states_at_the_cut(tmp_path, monkeypatch,
                                         type_name):
    """Commits landing between the cut and the adopt are in the suffix
    and in no seed; reopening from checkpoint + suffix equals the host
    replay of the same effects, and so does a second checkpoint
    stacked on the first.  The checkpoint is held between its two
    holds: at the decode for the device folds (the counts are back, so
    the commits publish), at the log sync for set_rw, whose lossy fold
    is read from the log under the cut's hold."""
    cfg, node = _node(tmp_path)
    pm = node.partitions[0]
    h = _History(node, type_name)
    for r in range(3):
        h.write(h.keys, f"r{r}")
    assert all(pm.device.owns(type_name, k) for k in h.keys)
    assert pm.checkpoint_now() is not None
    written = h.keys[:2]
    h.write(written, "before")
    at_cut = h.states()
    frontiers = {k: dict(pm.key_frontier[k]) for k in h.keys}
    cut = pm.log.log.end_offset()
    below = set(h.txids)
    gate = _Gate(pm)
    _patch_fold(monkeypatch, post=gate)
    real_sync = pm.log.log.sync
    monkeypatch.setattr(pm.log.log, "sync",
                        lambda: (gate(), real_sync())[1])
    thread, box = gate.start_checkpoint()
    try:
        during = h.write(written, "during")
    finally:
        gate.release.set()
    assert _done(thread) and box.get("result") is not None, box
    doc = box["result"]
    assert doc["cut_offset"] == cut
    assert {k: doc["keys"][k] for k in h.keys} == {
        k: (type_name, at_cut[k], frontiers[k]) for k in h.keys}
    assert all(pm.log.seed_for(k) == (type_name, at_cut[k],
                                      VC(frontiers[k])) for k in h.keys)
    assert set(written) <= set(pm._ckpt_dirty), \
        "a key written during the fold is clean: the next cut skips it"
    assert h.states() == h.reference()
    node.close()

    re = Node(dc_id="dc1", config=cfg)
    pm2 = re.partitions[0]
    assert pm2.log.suffix_start == cut
    suffix = {p.txid for _seq, p in pm2.log.suffix_payloads()}
    assert suffix == set(during) and not suffix & below
    assert h.states(pm2) == h.reference()
    # a second checkpoint, stacked on the recovered one
    h.node, h.pm = re, pm2
    h.write(h.keys[1:], "second")
    seeds_before = dict(pm2.log.ckpt_seeds)
    doc2 = pm2.checkpoint_now()
    assert {k for k in h.keys
            if pm2.log.ckpt_seeds[k] is not seeds_before[k]} \
        == set(h.keys)  # the first cut's "during" keys and these
    assert {k: doc2["keys"][k][1] for k in h.keys} == h.reference()
    h.write(h.keys[:1], "after")
    re.close()
    re2 = Node(dc_id="dc1", config=cfg)
    assert re2.partitions[0].log.suffix_start == doc2["cut_offset"]
    assert h.states(re2.partitions[0]) == h.reference()
    re2.close()


# ------------------------------------------------ (c) the adopt's cost


@pytest.mark.parametrize("segmented", [True, False],
                         ids=["segmented", "monolithic"])
def test_adopt_writes_the_dirty_seeds_and_no_others(tmp_path, segmented):
    """After a checkpoint of N keys, one with d dirty keys writes d
    seed entries on the segmented path — the others stay the objects
    they were — and says so on its spans; the first adopt after open
    and the monolithic twin build all N."""
    n, d = 40, 7
    cfg, node = _node(tmp_path, device_store=False,
                      ckpt_segmented=segmented)
    pm = node.partitions[0]
    keys = [f"ctr_{i}" for i in range(n)]
    for i, k in enumerate(keys):
        _commit(node, 33_000_000 + i, [(k, "counter_pn", i + 1)])
    rate = tracer.sample_rate
    tracer.sample_rate = 1.0
    try:
        tracer.clear()
        pm.checkpoint_now()
        assert tracer.spans(name="ckpt_adopt")[-1].args["seeds"] == n
        assert tracer.spans(name="ckpt_fold")[-1].args["dirty"] == n
        first = dict(pm.log.ckpt_seeds)
        dirty = keys[3:3 + d]
        for i, k in enumerate(dirty):
            _commit(node, 33_100_000 + i, [(k, "counter_pn", 100)])
        tracer.clear()
        doc = pm.checkpoint_now()
        assert tracer.spans(name="ckpt_fold")[-1].args["dirty"] == d
        written = tracer.spans(name="ckpt_adopt")[-1].args["seeds"]
    finally:
        tracer.sample_rate = rate
    seeds = pm.log.ckpt_seeds
    assert "delta" not in doc and len(doc["keys"]) == len(seeds) == n
    fresh = [k for k in keys if seeds[k] is not first[k]]
    if segmented:
        assert written == d and fresh == dirty
    else:
        assert written == n and fresh == keys
    for i, k in enumerate(keys):
        want = i + 1 + (100 if k in dirty else 0)
        tn, state, vc = pm.log.seed_for(k)
        assert (tn, state) == ("counter_pn", want)
        assert isinstance(vc, VC) and vc == doc["keys"][k][2]
    node.close()
    # a recovered document is a base like an adopted one: the live
    # seeds mirror its keys (_recover), and the next delta stacks on it
    re = Node(dc_id="dc1", config=cfg)
    pm2 = re.partitions[0]
    recovered = dict(pm2.log.ckpt_seeds)
    assert recovered == seeds
    _commit(re, 33_200_000, [(keys[0], "counter_pn", 1000)])
    pm2.checkpoint_now()
    fresh = [k for k in keys if pm2.log.ckpt_seeds[k] is not recovered[k]]
    assert fresh == (keys[:1] if segmented else keys)
    assert pm2.log.seed_for(keys[0])[1] == 1001
    re.close()


def test_first_segmented_adopt_after_a_monolithic_one_builds_all(
        tmp_path):
    """The first segmented cut after a monolithic document carries
    every seed in its delta (no segment holds them yet), and its adopt
    builds all; the one after is O(dirty) again."""
    n = 12
    cfg, node = _node(tmp_path, device_store=False, ckpt_segmented=False)
    keys = [f"ctr_{i}" for i in range(n)]
    for i, k in enumerate(keys):
        _commit(node, 34_000_000 + i, [(k, "counter_pn", 1)])
    node.partitions[0].checkpoint_now()
    node.close()
    cfg.ckpt_segmented = True
    re = Node(dc_id="dc1", config=cfg)
    pm = re.partitions[0]
    written = []
    real = pm.log.adopt_checkpoint
    pm.log.adopt_checkpoint = lambda *a: written.append(real(*a))
    _commit(re, 34_100_000, [(keys[0], "counter_pn", 1)])
    pm.checkpoint_now()
    _commit(re, 34_100_001, [(keys[1], "counter_pn", 1)])
    pm.checkpoint_now()
    assert written == [n, 1]
    assert [pm.log.seed_for(k)[1] for k in keys] == [2, 2] + [1] * (n - 2)
    re.close()


# ---------------------------------------- (d) a fold that fails outside


@pytest.mark.parametrize("half", ["fetch", "post"])
def test_a_failing_fold_leaves_nothing_held(tmp_path, monkeypatch, half):
    """An exception in the fold outside the lock — in the first plane's
    device half (the other plane's count still held) or in a decode
    (none is) — gives every reader count back, clears the in-flight
    mark and merges the dirty set back: the next publish does not wait
    and the next checkpoint folds those keys."""
    _cfg, node = _node(tmp_path)
    pm = node.partitions[0]
    ctr, aw = _History(node, "counter_pn"), _History(node, "set_aw")
    ctr.write(ctr.keys, "x")
    aw.write(aw.keys, "x")
    dirty = dict(pm._ckpt_dirty)
    assert set(dirty) == set(ctr.keys + aw.keys)

    def boom():
        raise RuntimeError("the fold fails")

    with monkeypatch.context() as m:
        _patch_fold(m, **{half: boom})
        t, got = _in_thread(pm.checkpoint_now, name=CKPT)
        assert _done(t)
        assert isinstance(got.get("error"), RuntimeError), got
    assert pm._dev_readers == 0 and not pm._ckpt_inflight
    assert pm._ckpt_dirty == dirty and pm.log.ckpt_doc is None
    ctr.write(ctr.keys[:1], "y")  # a device-route publish: no wait
    doc = pm.checkpoint_now()
    assert {k: doc["keys"][k][1] for k in ctr.keys} == ctr.reference()
    assert {k: doc["keys"][k][1] for k in aw.keys} == aw.reference()
    assert not pm._ckpt_dirty and pm._dev_readers == 0
    node.close()
