"""The one generator: seeded, the stated mix and key distribution, the
same seed gives the same stream, readers and writers alike draw their
keys over the whole keyspace through the mix's key generator:
``uniform_int``, or the source's ``pareto_int`` held to its closed
form.  The accepted mixes' streams are pinned by digest."""

import hashlib
import json
import os

import numpy as np
import pytest

from benchmark.traffic import ELEMS, ClientStream, Keyspace, Mix

#: the accepted cells' types (benchmark/configs/bb1dc.json)
TYPES = {"counter_pn": 3, "set_aw": 1}
KS = Keyspace.of(n_partitions=4, keys_per_partition=1024, types=TYPES)
#: the accepted cells' keyspace (benchmark/configs/bb1dc.json)
BB1DC = Keyspace.of(n_partitions=4, keys_per_partition=131072, types=TYPES)
N_CLIENTS = 16
PARETO = {"kind": "pareto_int"}
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def mix_file(tmp_path, **changes):
    doc = {"name": "m", "loop": "closed", "clients": N_CLIENTS,
           "operations": {"read_only_txn": 9, "update_only_txn": 1},
           "num_reads": 10, "num_updates": 10,
           "key_generator": {"kind": "uniform_int"}}
    doc.update(changes)
    path = tmp_path / "m.json"
    path.write_text(json.dumps(doc))
    return str(path)


def stream(tmp_path, seed=7, client=3, ks=KS, **changes):
    return ClientStream(Mix.from_file(mix_file(tmp_path, **changes)), ks,
                        seed, client)


def keys_of(txn):
    return txn.read_keys or [k for k, _o, _a in txn.updates]


either_generator = pytest.mark.parametrize(
    "generator", [{"kind": "uniform_int"}, PARETO],
    ids=["uniform_int", "pareto_int"])


def as_tuple(txn):
    return (txn.kind, tuple(txn.read_keys), tuple(txn.updates))


@either_generator
@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 3_000_000_019, -5])
def test_same_seed_same_stream(tmp_path, seed, generator):
    a = stream(tmp_path, seed=seed, key_generator=generator)
    b = stream(tmp_path, seed=seed, key_generator=generator)
    assert [as_tuple(a.next()) for _ in range(200)] \
        == [as_tuple(b.next()) for _ in range(200)]


@either_generator
def test_seeds_and_clients_differ(tmp_path, generator):
    first = [as_tuple(stream(tmp_path, seed=s, client=c,
                             key_generator=generator).next())
             for s in (1, 2) for c in (0, 1)]
    assert len(set(first)) == 4


@pytest.mark.parametrize("ops,share", [
    ({"read_only_txn": 9, "update_only_txn": 1}, 0.9),
    ({"read_only_txn": 1, "update_only_txn": 9}, 0.1),
    ({"read_only_txn": 1}, 1.0),
])
def test_the_stated_mix(tmp_path, ops, share):
    s = stream(tmp_path, operations=ops)
    kinds = [s.next().kind for _ in range(4000)]
    reads = kinds.count("read_only_txn") / len(kinds)
    assert abs(reads - share) < 0.02
    assert set(kinds) <= set(ops)


@either_generator
def test_ten_distinct_keys_a_transaction(tmp_path, generator):
    s = stream(tmp_path, key_generator=generator)
    for _ in range(500):
        keys = keys_of(s.next())
        assert len(keys) == 10 == len(set(keys))
        assert all(0 <= k < KS.n_keys for k in keys)


def test_updates_are_by_the_keys_type(tmp_path):
    s = stream(tmp_path, operations={"update_only_txn": 1})
    for _ in range(300):
        for key, op, arg in s.next().updates:
            if KS.type_of(key) == "counter_pn":
                assert op in ("increment", "decrement")
                assert 1 <= arg < 100
            else:
                assert op in ("add", "remove") and arg in ELEMS


@pytest.mark.parametrize("ops", [{"read_only_txn": 1},
                                 {"update_only_txn": 1}])
def test_every_client_draws_over_the_whole_keyspace(tmp_path, ops):
    # the source's uniform_int: a writer is held to no part of it
    for client in (0, 9):
        s = stream(tmp_path, client=client, operations=ops)
        keys = np.array([k for _ in range(3000)
                         for k in keys_of(s.next())])
        by_partition = np.bincount(keys % KS.n_partitions)
        assert by_partition.min() > 0.9 * by_partition.mean()
        # a flat histogram over sixteenths of the key range
        hist = np.histogram(keys, bins=16, range=(0, KS.n_keys))[0]
        assert hist.min() > 0.8 * hist.mean()
        sets = sum(KS.type_of(int(k)) == "set_aw" for k in keys)
        assert abs(sets / len(keys) - 0.25) < 0.02


def test_two_writers_meet_on_keys(tmp_path):
    a = stream(tmp_path, client=0, operations={"update_only_txn": 1})
    b = stream(tmp_path, client=1, operations={"update_only_txn": 1})
    wrote_a = {u[0] for _ in range(300) for u in a.next().updates}
    wrote_b = {u[0] for _ in range(300) for u in b.next().updates}
    assert wrote_a & wrote_b


def test_the_mix_file_states_how_an_abort_is_retried(tmp_path):
    assert Mix.from_file(mix_file(tmp_path)).retry_for_s == 0
    mix = Mix.from_file(mix_file(tmp_path, retry_for_s=10,
                                 retry_pause_ms=20))
    assert (mix.retry_for_s, mix.retry_pause_ms) == (10.0, 20.0)


@pytest.mark.parametrize("changes", [
    {"loop": "open"},
    {"operations": {"scan": 1}},
    {"operations": {}},
    {"operations": {"txn": 1}},
    {"key_generator": {"kind": "zipf"}},
    {"key_generator": {"kind": "truncated_pareto_int"}},
])
def test_a_mix_the_generator_cannot_make_is_refused(tmp_path, changes):
    with pytest.raises(ValueError):
        Mix.from_file(mix_file(tmp_path, **changes))


# ------------------------------------------------ the source's pareto_int

#: the closed form, N = 524,288: P(x >= k) = (1 + k / s) ** -1.5 with
#: s = trunc(0.2 N) / 2, over the draws below N (the others are drawn
#: again, the generator's one departure from the source, which gives
#: 0.808 and 0.932): 0.808 / 0.973, 0.932 / 0.973, and 1,000 x the
#: share of the first of 1,000 equal bins
BELOW_FIFTH, BELOW_HALF, HOTTEST_BIN = 0.831, 0.958, 15.4


@pytest.mark.parametrize("ops,seed,client", [
    ({"read_only_txn": 1}, 7, 0),
    ({"update_only_txn": 1}, 2**31 + 11, 9),
    ({"read_only_txn": 9, "update_only_txn": 1}, 3_000_000_019, 15),
])
def test_pareto_int_is_the_sources_closed_form(tmp_path, ops, seed, client):
    s = stream(tmp_path, seed=seed, client=client, ks=BB1DC,
               operations=ops, key_generator=PARETO)
    keys = np.array([k for _ in range(20_000) for k in keys_of(s.next())])
    n = BB1DC.n_keys
    assert len(keys) == 200_000
    assert keys.min() >= 0 and keys.max() < n     # none at or beyond N
    assert abs((keys < 0.2 * n).mean() - BELOW_FIFTH) < 0.005
    assert abs((keys < 0.5 * n).mean() - BELOW_HALF) < 0.004
    bins = np.histogram(keys, bins=1000, range=(0, n))[0]
    assert bins.argmax() == 0                     # low keys are hot
    assert abs(bins[0] / (len(keys) / 1000) - HOTTEST_BIN) < 1.0
    # the key drawn IS the key, so the hot set lies evenly on the four
    # partitions (key % 4) and 3:1 on the two types (row % 4)
    hot = keys[keys < 0.2 * n]
    by_partition = np.bincount(hot % BB1DC.n_partitions)
    assert by_partition.min() > 0.97 * by_partition.mean()
    sets = np.mean([BB1DC.type_of(int(k)) == "set_aw"
                    for k in hot[:40_000]])
    assert abs(sets - 0.25) < 0.01


@pytest.mark.parametrize("entry", [
    # the source's constants are not a mix's to set
    {"kind": "pareto_int", "shape": 3.0},
    {"kind": "pareto_int", "mean_frac": 0.2, "shape": 1.5,
     "beyond": "redraw"},
    {"kind": "uniform_int", "shape": 1.5},
    {"kind": "zipfian"},
    {},
    "pareto_int",
])
def test_a_malformed_key_generator_is_refused_with_the_files_path(
        tmp_path, entry):
    path = mix_file(tmp_path, key_generator=entry)
    with pytest.raises(ValueError, match="m.json"):
        Mix.from_file(path)


def test_a_mean_under_one_key_is_refused_when_the_cell_is_loaded(tmp_path):
    with pytest.raises(ValueError, match="under one key"):
        stream(tmp_path, ks=Keyspace.of(1, 4, TYPES), key_generator=PARETO)
    # and before set-up: where the mix and the keyspace first meet
    from bench_tiny import tiny_tree

    from benchmark import harness

    root = tiny_tree(str(tmp_path / "tree"), keys_per_partition=2)
    harness.load_cell(root, "bb1dc.read90-uniform")
    with pytest.raises(ValueError, match="under one key"):
        harness.load_cell(root, "bb1dc.read90-pareto")


def test_the_new_mix_is_its_sibling_with_another_key_generator():
    docs = {}
    for name in ("read90-uniform", "read90-pareto"):
        path = os.path.join(ROOT, "benchmark", "traffic", name + ".json")
        with open(path) as f:
            docs[name] = json.load(f)
        assert Mix.from_file(path).name == name
    a, b = docs["read90-uniform"], docs["read90-pareto"]
    assert b["key_generator"] == PARETO
    for key in a:
        if key not in ("name", "key_generator", "source", "assumed"):
            assert a[key] == b[key], key
    # what the file assumes is said in the file: the formula from
    # memory, the redraw (named in ``source`` as the one departure) with
    # its reason, and all its sibling assumes
    assert set(a["assumed"]) < set(b["assumed"])
    said = " ".join(b["assumed"])
    assert "from memory" in said and "drawn again" in said
    assert "pareto_int" in b["source"] and "1.5" in b["source"]
    assert "ONE DEPARTURE" in b["source"] and "83 %" in b["source"]
    assert "trunc(" in b["formula"]


def test_load_cell_finds_the_skewed_cell_with_its_siblings_metrics():
    from benchmark import harness

    a = harness.load_cell(ROOT, "bb1dc.read90-uniform")
    b = harness.load_cell(ROOT, "bb1dc.read90-pareto")
    assert b.mix.key_generator == PARETO and b.config == a.config
    assert b.keyspace == a.keyspace == BB1DC and b.chips == 1
    assert b.end_to_end == a.end_to_end and b.per_layer == a.per_layer
    assert [m["name"] for m in b.end_to_end] == [
        "txn_per_s", "read_p95_ms", "update_p95_ms", "setup_s"]
    assert {m["name"] for m in b.per_layer} == {
        "read_cache_hit_pct", "read_dispatches_per_read",
        "compiles_in_window", "ops_per_flush", "kernels_roofline",
        "device_idle_pct", "frontend_self_ms_per_txn",
        "serve_queue_wait_p95_ms", "manager_wait_ms_per_txn",
        "device_host_ms_per_dispatch", "host_busy_pct"}
    assert set(b.readers) == set(a.readers)


# --------------------------------- the accepted cells' traffic cannot drift

#: sha256 over the first 2,000 transactions of a ``(seed, client)`` at
#: the accepted cells' keyspace: the uniform mixes' computed on PR 34's
#: tree (the parent of the PR that made the key generator data), the
#: skewed mix's on the tree PR 35 measured its cell on: the stream of
#: an accepted mix is the same bits whatever is added beside it
PINNED = {
    ("read90-uniform", 7, 0):
        "d7670c8c6d6ce34d71ebbb3bd8c4bebbb92d739d3cb8f164324e8d8d0c05b7de",
    ("read90-uniform", 2147483659, 5):
        "38ca0a79ff01abc85b133f90c1c841d0a4f4e6905a789eb40679a19ca3f8382d",
    ("read90-uniform", 3000000019, 15):
        "99920c802724805af6601231f93e5400b339a21b1d8c57c870559349ab77110b",
    ("update90-uniform", 7, 0):
        "673278143738ad8705aaa181b0955f0092cfde347aea729f4b73ba1dd6e15262",
    ("update90-uniform", 2147483659, 5):
        "053570bd6c0b3d95048001805328b22846e57d31a7a71705b55d29dcf92f61ae",
    ("update90-uniform", 3000000019, 15):
        "8e723c9fb7b610f255f56d249a05df207e43edc0913dc624e4ddd7acbd29148e",
    ("read90-pareto", 7, 0):
        "3dfb8971842662898d9cc715883b08093d59d5f58c7b70ae05b7d6683377dfad",
    ("read90-pareto", 2147483659, 5):
        "84ef89654146a6df1d46de50f3b15bb8dfdb4cd4d70ba1893aa7773308f1d8e6",
    ("read90-pareto", 3000000019, 15):
        "705f937cddb70312614fa4fcacfb446f0d26db766cc3ca0c78802b7a8961e075",
}


@pytest.mark.parametrize("mix,seed,client", list(PINNED))
def test_an_accepted_mixs_stream_is_pinned(mix, seed, client):
    s = ClientStream(Mix.from_file(os.path.join(
        ROOT, "benchmark", "traffic", mix + ".json")), BB1DC, seed, client)
    h = hashlib.sha256()
    for _ in range(2000):
        h.update(repr(as_tuple(s.next())).encode())
    assert h.hexdigest() == PINNED[(mix, seed, client)]


def test_the_load_is_fixed_by_the_seed():
    a, b = KS.load_values(2**31 + 5), KS.load_values(2**31 + 5)
    assert (a[0] == b[0]).all() and (a[1] == b[1]).all()
    assert (KS.load_values(1)[0] != a[0]).any()
    bound, op, arg = KS.load_update(3 * KS.n_partitions, a)
    assert bound[1] == "set_aw" and op == "add_all" and 1 <= len(arg) <= 4
