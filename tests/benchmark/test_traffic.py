"""The one generator: seeded, the stated mix and key distribution, the
same seed gives the same stream, readers and writers alike draw their
keys over the whole keyspace."""

import json

import numpy as np
import pytest

from benchmark.traffic import ELEMS, ClientStream, Keyspace, Mix

KS = Keyspace(n_partitions=4, keys_per_partition=1024)
N_CLIENTS = 16


def mix_file(tmp_path, **changes):
    doc = {"name": "m", "loop": "closed", "clients": N_CLIENTS,
           "operations": {"read_only_txn": 9, "update_only_txn": 1},
           "num_reads": 10, "num_updates": 10,
           "key_generator": {"kind": "uniform_int"}}
    doc.update(changes)
    path = tmp_path / "m.json"
    path.write_text(json.dumps(doc))
    return str(path)


def stream(tmp_path, seed=7, client=3, **changes):
    return ClientStream(Mix.from_file(mix_file(tmp_path, **changes)), KS,
                        seed, client)


def as_tuple(txn):
    return (txn.kind, tuple(txn.read_keys), tuple(txn.updates))


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 3_000_000_019, -5])
def test_same_seed_same_stream(tmp_path, seed):
    a = stream(tmp_path, seed=seed)
    b = stream(tmp_path, seed=seed)
    assert [as_tuple(a.next()) for _ in range(200)] \
        == [as_tuple(b.next()) for _ in range(200)]


def test_seeds_and_clients_differ(tmp_path):
    first = [as_tuple(stream(tmp_path, seed=s, client=c).next())
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


def test_ten_distinct_keys_a_transaction(tmp_path):
    s = stream(tmp_path)
    for _ in range(500):
        t = s.next()
        keys = t.read_keys or [k for k, _o, _a in t.updates]
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
        keys = np.array([k for _ in range(3000) for t in [s.next()]
                         for k in t.read_keys
                         or [u[0] for u in t.updates]])
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
    {"key_generator": {"kind": "pareto_int"}},
])
def test_a_mix_the_generator_cannot_make_is_refused(tmp_path, changes):
    with pytest.raises(ValueError):
        Mix.from_file(mix_file(tmp_path, **changes))


def test_the_load_is_fixed_by_the_seed():
    a, b = KS.load_values(2**31 + 5), KS.load_values(2**31 + 5)
    assert (a[0] == b[0]).all() and (a[1] == b[1]).all()
    assert (KS.load_values(1)[0] != a[0]).any()
    bound, op, arg = KS.load_update(3 * KS.n_partitions, *a)
    assert bound[1] == "set_aw" and op == "add_all" and 1 <= len(arg) <= 4
