"""What the accepted cells read is the same bits whatever the harness
learns beside them: for every configuration and mix of ``bb1dc``,
``bb1dc-sync`` and ``bb2dc``, the keys' types, the load's writes, every
client's transaction stream, the pattern warm-up's reads, the
reference's answers (and its control's) and the bytes the work needs.

``pinned_parent.json`` holds their sha256 digests, computed on commit
c9d4d87, the tree before the harness read a configuration's ``types``
and before ``ycsb_zipfian``: seeds 1, 2 and 3, the first 2,000
transactions of clients 0-3, at the tiny tree's size (2 partitions of
4,096 keys) and at the cells' own (4 of 131,072)."""

import collections
import hashlib
import json
import os
import types as pytypes

import numpy as np
import pytest

from benchmark import harness, reference, trace
from benchmark.traffic import ClientStream, Keyspace, Mix

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
with open(os.path.join(HERE, "pinned_parent.json")) as f:
    PINNED = json.load(f)
#: the accepted configurations and the mixes their cells run
ACCEPTED = {"bb1dc": ("read90-uniform", "update90-uniform",
                      "read90-pareto"),
            "bb1dc-sync": ("update90-uniform",),
            "bb2dc": ("update90-probed",)}
SIZES = {"tiny": (2, 4096), "real": (4, 131072)}
SEEDS = (1, 2, 3)
CLIENTS = range(4)


def config(name: str) -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           name + ".json")) as f:
        return json.load(f)


def keyspace(name: str, size: str) -> Keyspace:
    return Keyspace.of(*SIZES[size], config(name)["types"], name)


def mix(name: str) -> Mix:
    return Mix.from_file(os.path.join(ROOT, "benchmark", "traffic",
                                      name + ".json"))


def sha(items) -> str:
    h = hashlib.sha256()
    for item in items:
        h.update(repr(item).encode())
        h.update(b"\n")
    return h.hexdigest()


def records(m: Mix, ks: Keyspace, seed: int, n: int = 500) -> list:
    """The four clients' first ``n`` transactions each, in turns, as
    acknowledged records with a commit or snapshot time one apart."""
    streams = [ClientStream(m, ks, seed, c) for c in CLIENTS]
    out, t = [], 1000
    for _ in range(n):
        for c, s in enumerate(streams):
            txn = s.next()
            t += 1
            out.append({
                "client": c, "kind": txn.kind, "ok": True, "dc": "dc1",
                "read_keys": txn.read_keys, "updates": txn.updates,
                "values": None, "clock_sent": t - 7, "aborts": 0,
                "snapshot_time": t if txn.read_keys else None,
                "commit_time": None if txn.read_keys else t,
                "t_send": float(t), "t_done": float(t) + 0.5})
    return out


class SeenClient:
    """Stands in for the wire client: keeps what the warm-up reads."""

    seen: list = []

    def __init__(self, *a, **kw):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False

    def read_objects_static(self, clock, bounds):
        SeenClient.seen.append(list(bounds))
        return [None] * len(bounds), None


def fake_db(lanes: int = 4, cap: int = 64):
    """Planes of fixed shapes: the harness's arithmetic, not the
    program's layout, is what is pinned."""
    St = collections.namedtuple("St", "base extra ops valid n")
    planes = {}
    for name, (kw, ow) in {"counter_pn": (3, 6), "set_aw": (9, 11),
                           "register_lww": (4, 7),
                           "set_go": (5, 6)}.items():
        planes[name] = pytypes.SimpleNamespace(st=St(
            np.zeros((cap, kw), np.int64), np.zeros((cap,), np.int32),
            np.zeros((cap * lanes, ow), np.int64),
            np.zeros((cap * lanes,), np.bool_), np.zeros((), np.int64)))
    part = pytypes.SimpleNamespace(device=pytypes.SimpleNamespace(
        planes=planes))
    return pytypes.SimpleNamespace(node=pytypes.SimpleNamespace(
        config=pytypes.SimpleNamespace(device_lanes=lanes),
        partitions=[part, part]))


def by_config_and_size():
    return [(c, s) for c in ACCEPTED for s in SIZES]


def by_cell_and_size():
    return [(c, m, s) for c, mixes in ACCEPTED.items() for m in mixes
            for s in SIZES]


@pytest.mark.parametrize("name,size", by_config_and_size())
def test_types_and_load_are_the_parents(name, size):
    ks = keyspace(name, size)
    assert sha(ks.type_of(k) for k in range(ks.n_keys)) \
        == PINNED[f"types/{size}"]
    for seed in SEEDS:
        load = ks.load_values(seed)
        assert sha(ks.load_update(k, load) for k in range(ks.n_keys)) \
            == PINNED[f"load/{size}/{seed}"], seed


@pytest.mark.parametrize("name,size", by_config_and_size())
def test_warm_up_reads_are_the_parents(monkeypatch, name, size):
    import antidote_tpu.pb.client as pb_client

    monkeypatch.setattr(pb_client, "PbClient", SeenClient)
    for seed in SEEDS:
        dep = harness.Deployment.__new__(harness.Deployment)
        dep.ks, dep.data_seed = keyspace(name, size), seed
        dep.cell = pytypes.SimpleNamespace(mix=mix(ACCEPTED[name][0]))
        dep.compiles = pytypes.SimpleNamespace(programs=0)
        SeenClient.seen = []
        dep._warm_patterns(0)
        assert sha(SeenClient.seen) == PINNED[f"warm/{size}/{seed}"], seed


@pytest.mark.parametrize("name,mix_name,size", by_cell_and_size())
def test_streams_are_the_parents(name, mix_name, size):
    ks, m = keyspace(name, size), mix(mix_name)
    for seed in SEEDS:
        for c in CLIENTS:
            s = ClientStream(m, ks, seed, c)
            got = sha((t.kind, tuple(t.read_keys), tuple(t.updates))
                      for t in (s.next() for _ in range(2000)))
            assert got == PINNED[f"stream/{mix_name}/{size}/{seed}/{c}"], \
                (seed, c)


@pytest.mark.parametrize("name,mix_name,size", by_cell_and_size())
def test_reference_answers_and_needed_bytes_are_the_parents(
        monkeypatch, name, mix_name, size):
    monkeypatch.setattr(trace, "xplane_of", lambda log_dir: log_dir)
    monkeypatch.setattr(trace, "reduce_xplane", lambda path: {})
    ks, m = keyspace(name, size), mix(mix_name)
    for seed in SEEDS:
        recs = records(m, ks, seed)
        h = reference.PlainHistory(ks, ks.load_values(seed))
        reference.feed(h, recs)
        answers = [[h.at(k, r["snapshot_time"]) for k in r["read_keys"]]
                   for r in recs if r["read_keys"]]
        written = list(dict.fromkeys(
            k for r in recs for k, _o, _a in r["updates"]))
        answers.append([h.at(k) for k in written[:300]])
        for r in recs:
            r["values"] = [h.at(k, r["snapshot_time"])
                           for k in r["read_keys"]]
        answers.append(reference.control_numbers(
            h, recs, {"keys": written[:300]}))
        assert sha(answers) == PINNED[f"reference/{mix_name}/{size}/{seed}"]
        dep = harness.Deployment.__new__(harness.Deployment)
        dep.ks, dep.db = ks, fake_db()
        dep.cell = pytypes.SimpleNamespace(
            dcs=int(config(name).get("dcs", 1)))
        reduced = dep._reduce({"log_dir": "x", "t0": 0.0, "t1": 1e12},
                              recs, {"kind": "TPU v5 lite"})
        assert repr(reduced["needed_bytes"]) \
            == PINNED[f"needed/{mix_name}/{size}/{seed}"]
    assert repr(trace.plane_row_bytes(fake_db())) == PINNED["rows/fake"]
