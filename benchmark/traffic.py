"""The one traffic generator: a keyspace, a mix read from a data file,
and one seeded transaction stream per client.

A mix is a JSON file under ``benchmark/traffic/`` that names what
basho_bench's ``antidote_pb`` driver names: closed-loop workers, weighted
operations, keys per transaction, a key generator.  Nothing here knows a
mix by name; a later PR adds a mix as a file.

Every client draws the keys it reads and the keys it updates
``uniform_int`` over the whole keyspace, as the source's workers do, so
two writers can meet on a key and write-write certification can abort
one of them; the client sends an aborted transaction again (client.py).
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field

import numpy as np

ELEMS = tuple(b"e%d" % i for i in range(6))
BUCKET = "bench"
#: the DC every write originates at: the reference keeps its histories
#: on this entry of the commit and snapshot clocks
ORIGIN_DC = "dc1"
#: a key's row within its partition decides its type: the fourth of
#: every four rows is a set, so counters and sets stand 3:1 everywhere
TYPE_PERIOD = 4


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """``--seed`` is any whole number up to a little over 2**31."""
    return np.random.default_rng([abs(int(seed)), *stream])


@dataclass(frozen=True)
class Keyspace:
    """Integer keys ``0 .. n_keys``; key ``k`` lives in partition
    ``k % n_partitions`` (txn/node.py ``partition_index``) at row
    ``k // n_partitions``."""

    n_partitions: int
    keys_per_partition: int

    @property
    def n_keys(self) -> int:
        return self.n_partitions * self.keys_per_partition

    def type_of(self, key: int) -> str:
        row = key // self.n_partitions
        return "set_aw" if row % TYPE_PERIOD == TYPE_PERIOD - 1 \
            else "counter_pn"

    def bound(self, key: int) -> tuple:
        return (key, self.type_of(key), BUCKET)

    def load_values(self, seed: int):
        """What the load writes to every key: a counter's first
        increment, a set's first one to four elements (as a bit mask
        over ``ELEMS``)."""
        rng = rng_for(seed, 0)
        incs = rng.integers(1, 1000, size=self.n_keys)
        masks = rng.integers(1, 16, size=self.n_keys)
        return incs, masks

    def load_update(self, key: int, incs, masks) -> tuple:
        if self.type_of(key) == "counter_pn":
            return (self.bound(key), "increment", int(incs[key]))
        m = int(masks[key])
        return (self.bound(key), "add_all",
                [e for i, e in enumerate(ELEMS) if m >> i & 1])


@dataclass(frozen=True)
class Mix:
    """One traffic mix, as its file states it."""

    name: str
    clients: int
    operations: dict
    num_reads: int
    num_updates: int
    key_generator: dict = field(default_factory=lambda: {
        "kind": "uniform_int"})
    #: a transaction that certification aborts is sent again after
    #: ``retry_pause_ms``, until ``retry_for_s`` after its first send
    retry_for_s: float = 0.0
    retry_pause_ms: float = 0.0

    KINDS = ("read_only_txn", "update_only_txn")

    @classmethod
    def from_file(cls, path: str) -> "Mix":
        with open(path) as f:
            doc = json.load(f)
        if doc.get("loop", "closed") != "closed":
            raise ValueError(f"{path}: only closed loops are generated")
        unknown = set(doc["operations"]) - set(cls.KINDS)
        if unknown or not doc["operations"]:
            raise ValueError(f"{path}: unknown operations {unknown}")
        if doc["key_generator"]["kind"] != "uniform_int":
            raise ValueError(f"{path}: unknown key generator "
                             f"{doc['key_generator']}")
        return cls(name=doc["name"], clients=int(doc["clients"]),
                   operations=dict(doc["operations"]),
                   num_reads=int(doc["num_reads"]),
                   num_updates=int(doc["num_updates"]),
                   key_generator=dict(doc["key_generator"]),
                   retry_for_s=float(doc.get("retry_for_s", 0.0)),
                   retry_pause_ms=float(doc.get("retry_pause_ms", 0.0)))


@dataclass
class Txn:
    """``kind`` with the keys it reads and the updates it sends."""

    kind: str
    read_keys: list
    updates: list  # (key, op, arg)


class ClientStream:
    """Client ``client``'s transactions, fixed by ``(seed, client)``:
    the n-th transaction is the same whatever the timing."""

    def __init__(self, mix: Mix, ks: Keyspace, seed: int, client: int):
        self.mix, self.ks = mix, ks
        self.rng = rng_for(seed, 1, client)
        kinds = [k for k in Mix.KINDS if mix.operations.get(k)]
        w = np.array([mix.operations[k] for k in kinds], dtype=float)
        self._kinds, self._cum = kinds, np.cumsum(w / w.sum())

    def _keys(self, n: int) -> list:
        """``n`` distinct keys, uniform over the whole keyspace."""
        out: dict = {}
        while len(out) < n:
            for k in self.rng.integers(0, self.ks.n_keys, size=n):
                out.setdefault(int(k), None)
                if len(out) == n:
                    break
        return list(out)

    def _update(self, key: int) -> tuple:
        if self.ks.type_of(key) == "counter_pn":
            op = "increment" if self.rng.random() < 0.7 else "decrement"
            return (key, op, int(self.rng.integers(1, 100)))
        op = "add" if self.rng.random() < 0.5 else "remove"
        return (key, op, ELEMS[int(self.rng.integers(len(ELEMS)))])

    def next(self, kind: str | None = None) -> Txn:
        """The next transaction; ``kind`` forces one of the mix's
        operations (the warm-up's read phases)."""
        mix = self.mix
        if kind is None:
            kind = self._kinds[int(np.searchsorted(
                self._cum, self.rng.random(), side="right").clip(
                    0, len(self._kinds) - 1))]
        if kind == "read_only_txn":
            return Txn(kind, self._keys(mix.num_reads), [])
        return Txn(kind, [], [self._update(k)
                              for k in self._keys(mix.num_updates)])
