"""The one traffic generator: a keyspace, a mix read from a data file,
and one seeded transaction stream per client.

A mix is a JSON file under ``benchmark/traffic/`` that names what
basho_bench's ``antidote_pb`` driver names: closed-loop workers, weighted
operations, keys per transaction, a key generator.  Nothing here knows a
mix by name; a later PR adds a mix as a file.

Every client draws the keys it reads and the keys it updates through
the mix's one key generator over the whole keyspace, as the source's
workers do, so two writers can meet on a key and write-write
certification can abort one of them; the client sends an aborted
transaction again (client.py).

The key generators are basho_bench's (``basho_bench_keygen.erl``), as
the source spells them and with the source's constants: a mix names a
kind and sets nothing: ``uniform_int``, and ``pareto_int``, whose draw
is the key itself, so low keys are hot.
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


#: ``pareto_int``'s constants are the source's own, compiled into
#: ``basho_bench_keygen.erl`` (``?PARETO_SHAPE``, and the mean as a share
#: of ``MaxKey``): a mix does not set them
PARETO_SHAPE = 1.5
PARETO_MEAN_FRAC = 0.2


def _uniform_int(n_keys: int):
    return lambda rng, n: rng.integers(0, n_keys, size=n)


def _pareto_int(n_keys: int):
    """The source's ``{pareto_int, MaxKey}``, its ``pareto/2``:
    ``trunc((U^(-1/Shape) - 1) * Mean * (Shape - 1))`` with ``Mean =
    trunc(0.2 * MaxKey)`` and ``U`` uniform on (0, 1]: a fifth of the
    keys takes four fifths of the draws.  The one departure: the source
    lets a draw at or beyond ``MaxKey`` through as a new key (2.7 % of
    the draws); here it is dropped and drawn again, so the lowest fifth
    of the keys takes 83 % and ``draw`` returns ``n`` keys or fewer.
    Let through, such keys are served and read right, but in 8 of 15
    runs on the chip a read program then compiled inside the window
    (PERF.md, PR 35): the warm-up knows the rows the load wrote."""
    mean = int(PARETO_MEAN_FRAC * n_keys)
    if mean < 1:
        raise ValueError(f"pareto_int over {n_keys} keys: a mean of "
                         f"{PARETO_MEAN_FRAC} of them is under one key, "
                         "and every draw would be key 0")

    def draw(rng, n):
        u = 1.0 - rng.random(size=n)
        x = np.trunc((u ** (-1.0 / PARETO_SHAPE) - 1.0)
                     * mean * (PARETO_SHAPE - 1.0))
        return x[x < n_keys].astype(np.int64)

    return draw


#: kind -> the draw over a keyspace of ``n_keys``: ``draw(rng, n)`` gives
#: up to ``n`` keys.  A mix's entry is ``{"kind": <kind>}`` and no more
KEY_GENERATORS = {"uniform_int": _uniform_int, "pareto_int": _pareto_int}


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
    #: closed-loop probers beside the ``clients``: each commits one
    #: update of ``num_updates`` keys at the origin DC and, once it is
    #: acknowledged, reads the same keys at the next DC at the commit
    #: clock it returned (a configuration with ``dcs`` 2 or more)
    probers: int = 0

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
        known = [{"kind": kind} for kind in KEY_GENERATORS]
        if doc["key_generator"] not in known:
            raise ValueError(f"{path}: key generator "
                             f"{doc['key_generator']} is none of {known}")
        return cls(name=doc["name"], clients=int(doc["clients"]),
                   operations=dict(doc["operations"]),
                   num_reads=int(doc["num_reads"]),
                   num_updates=int(doc["num_updates"]),
                   key_generator=dict(doc["key_generator"]),
                   retry_for_s=float(doc.get("retry_for_s", 0.0)),
                   retry_pause_ms=float(doc.get("retry_pause_ms", 0.0)),
                   probers=int(doc.get("probers", 0)))


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
        self._draw = KEY_GENERATORS[mix.key_generator["kind"]](ks.n_keys)

    def _keys(self, n: int) -> list:
        """``n`` distinct keys over the whole keyspace, each a draw of
        the mix's key generator."""
        out: dict = {}
        while len(out) < n:
            for k in self._draw(self.rng, n):
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
