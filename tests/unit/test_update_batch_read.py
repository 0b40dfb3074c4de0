"""An update reads the state it needs once (PR 29):
``Coordinator._apply_updates`` checks every operation of a call, reads
the state its state-requiring keys need in ONE batched snapshot read
(``mat.serve.read_groups``), generates the downstreams in the caller's
order and stages once per partition.

(a) the programs launched and the stagings of a 10-key static update;
(b) the same effects, log records and states as the per-operation loop
it replaced, kept here as test-side code; (c) a lossy type still reads
exact; (d) a bad operation leaves nothing behind; (e) which path the
counter says each key took."""

import itertools
import random

import pytest

from antidote_tpu import stats
from antidote_tpu.api import AntidoteTPU
from antidote_tpu.config import Config
from antidote_tpu.crdt import DownstreamError, get_type, is_type
from antidote_tpu.mat import device_plane
from antidote_tpu.obs.spans import tracer
from antidote_tpu.txn.coordinator import (
    Coordinator,
    TransactionAborted,
    TxnState,
)

CK, SK = "counter_pn", "set_aw"
N_PARTS = 4


def open_db(path):
    """Four partitions whose value cache holds nothing, so that a read
    of a device-resident key asks the device; dots from a counter, so
    that two deployments fed the same operations mint the same dots."""
    db = AntidoteTPU(dc_id="dc1", data_dir=str(path),
                     config=Config(n_partitions=N_PARTS, metrics_port=None,
                                   device_lanes=64))
    for pm in db.node.partitions:
        pm._val_cache_cap = 0
        pm.seed_cache_on_first_publish = False
    seq = itertools.count(1)
    db.node.mint_dot = lambda: ("dc1", next(seq))
    return db


@pytest.fixture
def db(tmp_path):
    db = open_db(tmp_path / "d")
    yield db
    db.close()


def keys_on(db, partition, type_name, n):
    """``n`` bound objects of ``type_name`` that ``partition`` owns."""
    out = []
    for i in itertools.count():
        key = f"{type_name}.{partition}.{i}"
        if db.node.partition_of(key).partition == partition:
            out.append((key, type_name, "b"))
            if len(out) == n:
                return out


def reads_by_path():
    reg = stats.registry.update_state_reads
    return reg.value(path="batched"), reg.value(path="single")


def apply_updates_per_operation(self, tx, updates):
    """``Coordinator._apply_updates`` as it stood before PR 29: one
    exact single-key read and one ``stage_update`` an operation."""
    for upd in updates:
        bo, op_name, op_param = self.node.normalize_update(upd)
        key, type_name, bucket = self.node.normalize_bound(bo)
        cls = get_type(type_name) if is_type(type_name) else None
        op = (op_name, op_param)
        if cls is None or not cls.is_operation(op):
            self.abort_transaction(tx)
            raise TypeError(f"type_check failed: {type_name} {op!r}")
        try:
            key2, type_name2, op = self.node.hooks.run_pre(
                bucket, key, type_name, op)
        except Exception as e:
            self.abort_transaction(tx)
            raise TransactionAborted(f"pre-commit hook failed: {e}") from e
        cls = get_type(type_name2)
        pm = self.node.partition_of(key2)
        assert not getattr(pm, "deferred_stage", False)  # local only
        try:
            state = None
            if cls.require_state_downstream(op):
                state = pm.read_with_writeset(
                    key2, cls.name, tx.snapshot_vc, tx.txid,
                    tx.own_effects(key2), exact_state=True)
            effect = self.node.gen_downstream(
                cls, op, state, tx.ctx, key=key2, bucket=bucket)
        except DownstreamError as e:
            self.abort_transaction(tx)
            raise TransactionAborted(f"downstream failed: {e}") from e
        pm.stage_update(tx.txid, key2, cls.name, effect)
        entry = tx.writeset.setdefault(key2, (cls.name, []))
        entry[1].append(effect)
        if pm.partition not in tx.partitions:
            tx.partitions.append(pm.partition)
        tx.client_ops.append((bucket, key2, cls.name, op))


def spy_stagings(db):
    """{partition: [(method, operations)]} of every staging call."""
    calls = {pm.partition: [] for pm in db.node.partitions}
    for pm in db.node.partitions:
        for name in ("stage_update", "stage_group"):
            def spy(txid, *a, _orig=getattr(pm, name), _name=name,
                    _p=pm.partition, **kw):
                calls[_p].append(
                    (_name, len(a[0]) if _name == "stage_group" else 1))
                return _orig(txid, *a, **kw)

            setattr(pm, name, spy)
    return calls


# ------------------------------ (a) one program a chip, one stage a partition


@pytest.mark.parametrize("k,p", [(0, 2), (1, 1), (3, 1), (2, 2), (4, 2),
                                 (4, 4), (8, 4), (10, 3)])
def test_a_static_update_reads_once_and_stages_once_a_partition(db, k, p):
    """Ten keys, ``k`` of them ``set_aw``, over ``p`` partitions."""
    parts = list(range(p))
    sets = [keys_on(db, parts[i % p], SK, i // p + 1)[i // p]
            for i in range(k)]
    counters = [keys_on(db, parts[i % p], CK, i // p + 1)[i // p]
                for i in range(10 - k)]
    db.update_objects_static(
        None, [(o, "add", "e0") for o in sets]
        + [(o, "increment", 1) for o in counters])
    assert all(db.node.partition_of(o[0]).device.owns(SK, o[0])
               for o in sets)
    # default placement: no partition is pinned, one chip holds all
    assert {pm.device.device for pm in db.node.partitions} == {None}
    updates = [(o, "add", "e1") for o in sets] \
        + [(o, "increment", 2) for o in counters]
    random.Random(k * 10 + p).shuffle(updates)
    stagings = spy_stagings(db)
    programs0 = device_plane.read_dispatch_count()
    captures0 = stats.registry.read_dispatches.value()
    paths0 = reads_by_path()
    tracer.clear()
    saved, tracer.sample_rate = tracer.sample_rate, 1.0
    try:
        db.update_objects_static(None, updates)
    finally:
        tracer.sample_rate = saved
    programs = device_plane.read_dispatch_count() - programs0
    captures = stats.registry.read_dispatches.value() - captures0
    set_parts = {db.node.partition_of(o[0]).partition for o in sets}
    # the parent: k single-key programs, none of them counted here, and
    # k captures; now one program for the chip and a capture a partition
    assert programs == (1 if k else 0)
    assert captures == len(set_parts)
    touched = {db.node.partition_of(o[0]).partition for o, _n, _a in updates}
    assert {q: c for q, c in stagings.items() if c} == {
        q: [("stage_group", sum(
            db.node.partition_of(o[0]).partition == q
            for o, _n, _a in updates))] for q in touched}
    batched, single = (a - b for a, b in zip(reads_by_path(), paths0))
    assert (batched, single) == (k, 0)
    spans = {s.name: s for s in tracer.spans()}
    assert "device_read" not in spans
    if k:
        read = spans["txn_state_read"]
        assert read.kind == "work" and read.args["keys"] == k
        assert read.args["partitions"] == len(set_parts)
        assert spans["txn_update"].span_id == read.parent_id
    else:
        assert "txn_state_read" not in spans
    values, _clock = db.read_objects_static(None, sets + counters)
    assert values == [["e0", "e1"]] * k + [3] * (10 - k)


# --------------------------- (b) the per-operation loop's effects, to the dot


def _objs(db):
    return {"s": [o for q in range(N_PARTS)
                  for o in keys_on(db, q, SK, 3)],
            "c": [o for q in range(N_PARTS)
                  for o in keys_on(db, q, CK, 3)]}


def case_same_key_twice(o, _rng):
    s, c = o["s"][0], o["c"][0]
    return [[[(s, "add", "x"), (c, "increment", 3), (s, "add", "y"),
              (c, "increment", 4), (s, "add", "x")]]]


def case_add_then_remove(o, _rng):
    s = o["s"][1]
    return [[[(s, "add", "kept")]],
            [[(s, "add", "gone"), (s, "remove", "gone"),
              (s, "remove", "kept"), (s, "add", "kept")]]]


def case_key_already_in_writeset(o, _rng):
    s, t = o["s"][2], o["s"][5]
    return [[[(s, "add", "a"), (t, "add", "a")],
             [(s, "remove", "a"), (s, "add", "b"), (t, "add_all", ["a", "c"])],
             [(t, "remove_all", ["a", "b"]), (s, "add", "a")]]]


def case_counters_only(o, rng):
    return [[[(c, "increment", rng.randint(1, 99))
              for c in rng.sample(o["c"], 10)]] for _ in range(3)]


def case_sets_only(o, rng):
    return [[[(s, rng.choice(["add", "remove"]), rng.randint(0, 5))
              for s in rng.sample(o["s"], 10)]] for _ in range(4)]


def case_seeded_mix(o, rng):
    txns = []
    for _ in range(8):
        calls = []
        for _c in range(rng.randint(1, 3)):
            call = []
            for obj in rng.choices(o["s"] + o["c"], k=rng.randint(1, 10)):
                if obj[1] == CK:
                    call.append((obj, "increment", rng.randint(1, 99)))
                else:
                    call.append((obj, rng.choice(["add", "remove"]),
                                 rng.randint(0, 5)))
            calls.append(call)
        txns.append(calls)
    return txns


def run_history(db, txns):
    """Each transaction a list of ``update_objects`` calls; returns what
    the log was handed, per partition in order, each transaction's
    writeset, partitions and client operations, and the final states."""
    logged = {pm.partition: [] for pm in db.node.partitions}
    for pm in db.node.partitions:
        def record(dc, txid, key, type_name, effect,
                   _orig=pm.log.append_update, _p=pm.partition):
            logged[_p].append((key, type_name, effect))
            return _orig(dc, txid, key, type_name, effect)

        pm.log.append_update = record
    seen = []
    clock = None
    for calls in txns:
        tx = db.start_transaction(clock)
        for call in calls:
            db.update_objects(call, tx)
        seen.append((dict(tx.writeset), list(tx.partitions),
                     list(tx.client_ops)))
        clock = db.commit_transaction(tx)
    states = {}
    for pm in db.node.partitions:
        with pm._lock:
            for key, type_name in {(k, t) for k, t, _e in
                                   logged[pm.partition]}:
                states[key] = pm._read_from_log(key, type_name, clock)
    values = {}
    for calls in txns:
        objs = [o for call in calls for o, _n, _a in call]
        for o, v in zip(objs, db.read_objects_static(clock, objs)[0]):
            values[o[0]] = v
    return logged, seen, states, values


@pytest.mark.parametrize("case", [
    case_same_key_twice, case_add_then_remove,
    case_key_already_in_writeset, case_counters_only, case_sets_only,
    case_seeded_mix], ids=lambda c: c.__name__[5:])
def test_the_batch_gives_the_per_operation_loops_effects_and_records(
        tmp_path, monkeypatch, case):
    new = open_db(tmp_path / "new")
    old = open_db(tmp_path / "old")
    try:
        txns = case(_objs(new), random.Random(29))
        assert txns == case(_objs(old), random.Random(29))
        got = run_history(new, txns)
        monkeypatch.setattr(Coordinator, "_apply_updates",
                            apply_updates_per_operation)
        want = run_history(old, txns)
        monkeypatch.undo()
        for name, g, w in zip(("log records", "writesets", "states",
                               "values"), got, want):
            assert g == w, name
        assert any(got[0].values())
        # and the device's fold of those log records agrees with the
        # host's replay of them
        assert got[3] == {
            key: get_type(key.split(".")[0]).value(state)
            for key, state in got[2].items()}
    finally:
        new.close()
        old.close()


# ------------------------------------- (c) a lossy fold still feeds no effect


@pytest.mark.parametrize("type_name,mark,cancel", [
    ("set_rw", ("add", "e"), ("remove", "e")),
    ("flag_dw", ("enable", None), ("disable", None))])
def test_a_lossy_type_in_the_batch_is_read_exact(db, type_name, mark,
                                                 cancel):
    lossy = keys_on(db, 0, type_name, 1)[0]
    sets = keys_on(db, 0, SK, 2) + keys_on(db, 1, SK, 1)
    # two marks in two transactions: two live dots of one DC, which the
    # device's fold collapses to the newer
    for _ in range(2):
        db.update_objects_static(None, [(lossy, *mark)])
    db.update_objects_static(None, [(o, "add", 1) for o in sets])
    pm = db.node.partition_of(lossy[0])
    assert pm.device.owns(type_name, lossy[0])
    assert not pm.device.state_exact(type_name, lossy[0])
    exact_reads = []
    orig = pm.read_with_writeset

    def spy(key, tn, vc, txid, own, exact_state=False):
        exact_reads.append((key, tn, exact_state))
        return orig(key, tn, vc, txid, own, exact_state=exact_state)

    pm.read_with_writeset = spy
    paths0 = reads_by_path()
    tx = db.start_transaction()
    db.update_objects([(sets[0], "add", 2), (lossy, *cancel),
                       (sets[2], "remove", 1), (sets[1], "add", 2)], tx)
    assert exact_reads == [(lossy[0], type_name, True)]
    batched, single = (a - b for a, b in zip(reads_by_path(), paths0))
    assert (batched, single) == (3, 1)
    effect = tx.writeset[lossy[0]][1][0]
    observed = set(effect[1][0][2] if type_name == "set_rw" else effect[2])
    assert len(observed) == 2, effect
    clock = db.commit_transaction(tx)
    with pm._lock:
        state = pm._read_from_log(lossy[0], type_name, clock)
    live = state["e"][0] if type_name == "set_rw" else state[0]
    assert live == frozenset()
    assert db.read_objects_static(clock, [lossy])[0] == [
        [] if type_name == "set_rw" else False]


# ------------------------------------ (d) a bad operation leaves nothing


@pytest.mark.parametrize("n", [0, 4, 9])
@pytest.mark.parametrize("bad,error,text", [
    (lambda o: (o, "no_such_op", 1), TypeError, "type_check failed"),
    (lambda o: ((o[0], "no_such_type", "b"), "add", 1), KeyError,
     "unknown CRDT type"),
    ("hook", TransactionAborted, "pre-commit hook failed"),
    (lambda o: ((o[0] + "b", "counter_b", "b"), "decrement", (5, "dc1")),
     TransactionAborted, "downstream failed")])
def test_a_bad_operation_aborts_with_nothing_staged_or_logged(
        db, n, bad, error, text):
    objs = keys_on(db, 0, SK, 5) + keys_on(db, 1, CK, 5)
    db.update_objects_static(None, [
        (o, "add", 0) if o[1] == SK else (o, "increment", 1) for o in objs])
    updates = [(o, "add", 1) if o[1] == SK else (o, "increment", 1)
               for o in objs]
    if bad == "hook":
        def refuse(key, type_name, op):
            if key == objs[n][0]:
                raise ValueError("refused")
            return key, type_name, op

        db.register_pre_hook("b", refuse)
    else:
        updates[n] = bad(objs[n])
    appended = []
    for pm in db.node.partitions:
        pm.log.append_update = lambda *a, **kw: appended.append(a)
    aborted0 = stats.registry.aborted_transactions.value()
    tx = db.start_transaction()
    with pytest.raises(error, match=text):
        db.update_objects(updates, tx)
    assert tx.state is TxnState.ABORTED and not tx.gated
    assert appended == []
    assert all(tx.txid not in pm._staged for pm in db.node.partitions)
    assert stats.registry.aborted_transactions.value() == aborted0 + 1
    assert [pm._dev_readers for pm in db.node.partitions] == [0] * N_PARTS


# -------------------------------------- (e) which path a key's read took


def test_only_what_the_batch_must_not_serve_reads_single(db):
    """Local exact keys never read single; a host-resident key, a key
    never written and a key the call updates three times are one
    batched key each."""
    sets = keys_on(db, 2, SK, 3) + keys_on(db, 3, SK, 2)
    db.update_objects_static(None, [(o, "add", 0) for o in sets[:4]])
    pm = db.node.partition_of(sets[0][0])
    with pm._lock:
        pm.device.planes[SK].evict(sets[0][0])
    assert not pm.device.owns(SK, sets[0][0])
    mv = keys_on(db, 2, "register_mv", 1)[0]
    paths0 = reads_by_path()
    clock = db.update_objects_static(None, [
        (sets[0], "add", 1), (sets[1], "add", 1), (sets[1], "remove", 0),
        (sets[1], "add", 2), (sets[4], "add", 1), (sets[3], "remove", 0),
        (mv, "assign", "v")])
    batched, single = (a - b for a, b in zip(reads_by_path(), paths0))
    assert (batched, single) == (5, 0)
    assert db.read_objects_static(clock, sets + [mv])[0] == [
        [0, 1], [1, 2], [0], [], [1], ["v"]]
    # what stays single: a map (its fold's exactness is its resident
    # fields'), a bounded counter
    m = keys_on(db, 1, "map_rr", 1)[0]
    b = keys_on(db, 1, "counter_b", 1)[0]
    db.update_objects_static(None, [(b, "increment", (9, "dc1"))])
    paths0 = reads_by_path()
    db.update_objects_static(None, [
        (m, "update", [(("f", SK), ("add", 1))]), (b, "decrement", (2, "dc1"))])
    batched, single = (a - b for a, b in zip(reads_by_path(), paths0))
    assert (batched, single) == (0, 2)
