"""The one traffic generator: a keyspace, a mix read from a data file,
and one seeded transaction stream per client.

A mix is a JSON file under ``benchmark/traffic/`` that names what
basho_bench's ``antidote_pb`` driver and YCSB's CoreWorkload name:
closed-loop workers, weighted operations, keys per transaction, a key
generator.  Nothing here knows a mix or a configuration by name; a
later PR adds either as a file.

Every client draws the keys it reads and the keys it updates through
the mix's one key generator over the whole keyspace, as the sources'
workers do, so two writers can meet on a key and write-write
certification can abort one of them; the client sends an aborted
transaction again (client.py).

The key generators are data: a mix names a kind and sets nothing, each
kind taken with its source's constants.  basho_bench's
(``basho_bench_keygen.erl``): ``uniform_int``, and ``pareto_int``, whose
draw is the key itself, so low keys are hot.  YCSB's
(``ScrambledZipfianGenerator``): ``ycsb_zipfian``, a Zipfian draw of
constant 0.99 over the source's fixed ten billion items, scrambled onto
the keyspace by a hash, so the hot keys lie anywhere.

The keyspace's types are data too: the configuration's ``types`` lays
them on rows by weight, and a type may be a record, a map of fields
(``RECORD_TYPES``, ``FIELD_TYPES``): a YCSB record is a map of ten
100-byte registers.
"""

from __future__ import annotations

import hashlib
import json
from dataclasses import dataclass, field
from typing import NamedTuple

import numpy as np

ELEMS = tuple(b"e%d" % i for i in range(6))
BUCKET = "bench"
#: the DC every write originates at: the reference keeps its histories
#: on this entry of the commit and snapshot clocks
ORIGIN_DC = "dc1"


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


#: ``ycsb_zipfian``'s constants are the source's own
#: (``ScrambledZipfianGenerator``: ``ITEM_COUNT``, ``ZETAN``,
#: ``USED_ZIPFIAN_CONSTANT``; ``Utils.fnvhash64``): a mix does not set
#: them.  The Zipfian draw runs over ``ZipfianGenerator(0, ITEM_COUNT)``,
#: whose item count is ``max - min + 1``
ZIPF_ITEMS = 10_000_000_000 + 1
ZIPF_THETA = 0.99
ZIPF_ZETAN = 26.46902820178302
FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211


def fnvhash64(x: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64`` of each value: 64-bit FNV-1a over its
    eight bytes, low first, then Java's ``Math.abs`` of the signed
    result."""
    h = np.full(x.shape, FNV_OFFSET_BASIS_64, dtype=np.uint64)
    v = x.astype(np.uint64)
    for _ in range(8):
        h ^= v & np.uint64(0xFF)
        h *= np.uint64(FNV_PRIME_64)
        v >>= np.uint64(8)
    return np.abs(h.view(np.int64))


def _ycsb_zipfian(n_keys: int):
    """The source's ``requestdistribution=zipfian``,
    ``ScrambledZipfianGenerator(0, n_keys - 1)``: Gray et al.'s Zipfian
    draw (SIGMOD 1994) of constant 0.99 over ``ZIPF_ITEMS`` with the
    precomputed zeta, then ``fnvhash64(x) mod n_keys``.  The hottest
    key takes ``1 / ZIPF_ZETAN`` = 3.78 % of the draws, the second
    ``0.5 ** 0.99`` of that; which keys they are the hash decides.
    Written from memory of YCSB's ``ScrambledZipfianGenerator``,
    ``ZipfianGenerator`` and ``Utils.fnvhash64``: no copy of the source
    is in this repository to hold it to."""
    n, theta, zetan = ZIPF_ITEMS, ZIPF_THETA, ZIPF_ZETAN
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / n) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)

    def draw(rng, k):
        u = rng.random(size=k)
        uz = u * zetan
        x = np.trunc(n * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
        x = np.where(uz < 1.0, 0, np.where(uz < zeta2, 1, x))
        return fnvhash64(x) % n_keys

    return draw


#: kind -> the draw over a keyspace of ``n_keys``: ``draw(rng, n)`` gives
#: up to ``n`` keys.  A mix's entry is ``{"kind": <kind>}`` and no more
KEY_GENERATORS = {"uniform_int": _uniform_int, "pareto_int": _pareto_int,
                  "ycsb_zipfian": _ycsb_zipfian}

#: the flat types a configuration's ``types`` may name: the harness
#: loads, updates and judges these
FLAT_TYPES = ("counter_pn", "set_aw")
#: the record types, maps of fields: ``map_go`` keeps a field once
#: written; ``map_rr`` resets a field on remove, so its fields' type
#: has to have a reset (crdt/maps.py ``MapRR``, antidote_crdt_map_rr)
RECORD_TYPES = ("map_go", "map_rr")
#: a record's field types, with whether each has a reset: the harness
#: assigns them whole values of ``field_bytes`` bytes
FIELD_TYPES = {"register_lww": False, "register_mv": True}
RECORD_KEYS = ("fields", "field_bytes", "weight")


@dataclass(frozen=True)
class Record:
    """A record type: its ``fields``, ``(name, field type)`` pairs
    named ``field0``, ``field1``, ... as YCSB names them, each holding
    ``field_bytes`` bytes."""

    fields: tuple
    field_bytes: int


def field_value(seed: int, key: int, field_name: str, n: int) -> bytes:
    """The ``n`` bytes the load writes to a record's field: fixed by
    ``--seed`` and the key."""
    return hashlib.shake_128(
        f"{int(seed)}/{key}/{field_name}".encode()).digest(n)


class Load(NamedTuple):
    """What the load writes, from ``--seed``: a counter's first
    increment and a set's first elements (as a bit mask over ``ELEMS``)
    by key; a record's fields from ``field_value``."""

    incs: np.ndarray
    masks: np.ndarray
    seed: int


def _refuse(path: str, what: str):
    raise ValueError(f"{path}: {what}")


@dataclass(frozen=True)
class Keyspace:
    """Integer keys ``0 .. n_keys``; key ``k`` lives in partition
    ``k % n_partitions`` (txn/node.py ``partition_index``) at row
    ``k // n_partitions``, and its row decides its type: ``layout``
    holds each type of the configuration's ``types`` as many times as
    its weight, in the file's order, and row ``r`` takes
    ``layout[r % len(layout)]``.  ``{"counter_pn": 3, "set_aw": 1}``
    makes three rows of every four counters and the fourth a set."""

    n_partitions: int
    keys_per_partition: int
    layout: tuple
    #: ``(type, Record)`` of each record type in ``layout``
    records: tuple = ()

    @classmethod
    def of(cls, n_partitions: int, keys_per_partition: int, types,
           path: str = "types") -> "Keyspace":
        """The keyspace a configuration's ``types`` describe; a type the
        harness cannot load, update and judge is refused with ``path``.
        A flat type's entry is its weight; a record type's is
        ``{"fields": {<field type>: <count>}, "field_bytes": <n>}``, with
        ``"weight"`` (default 1) where it shares the rows."""
        if not isinstance(types, dict) or not types:
            _refuse(path, f"types {types!r} names no type")
        layout, records = [], []
        for name, spec in types.items():
            if name in FLAT_TYPES:
                weight = spec
            elif name in RECORD_TYPES:
                records.append((name, cls._record(name, spec, path)))
                weight = spec.get("weight", 1)
            else:
                _refuse(path, f"type {name!r} is none of "
                              f"{FLAT_TYPES + RECORD_TYPES}")
            if type(weight) is not int or weight < 1:
                _refuse(path, f"type {name!r}: weight {weight!r} is not "
                              "a whole number of rows")
            layout += [name] * weight
        return cls(n_partitions, keys_per_partition, tuple(layout),
                   tuple(records))

    @staticmethod
    def _record(name: str, spec, path: str) -> Record:
        if not isinstance(spec, dict) or set(spec) - set(RECORD_KEYS) \
                or not {"fields", "field_bytes"} <= set(spec):
            _refuse(path, f"record type {name!r}: {spec!r} is not "
                          f"fields and field_bytes (and a weight)")
        fields = spec["fields"]
        size = spec["field_bytes"]
        if not isinstance(fields, dict) or not fields:
            _refuse(path, f"record type {name!r} has no fields")
        if type(size) is not int or size < 1:
            _refuse(path, f"record type {name!r}: field_bytes {size!r}")
        named = []
        for field_type, count in fields.items():
            if field_type not in FIELD_TYPES:
                _refuse(path, f"record type {name!r}: field type "
                              f"{field_type!r} is none of "
                              f"{tuple(FIELD_TYPES)}")
            if name == "map_rr" and not FIELD_TYPES[field_type]:
                _refuse(path, f"record type {name!r}: map_rr resets a "
                              f"removed field and {field_type!r} has no "
                              "reset, so every update of such a record "
                              "is refused; a map_go keeps its fields")
            if type(count) is not int or count < 1:
                _refuse(path, f"record type {name!r}: {count!r} fields "
                              f"of {field_type!r}")
            named += [(f"field{len(named) + i}", field_type)
                      for i in range(count)]
        return Record(tuple(named), size)

    @property
    def n_keys(self) -> int:
        return self.n_partitions * self.keys_per_partition

    def type_of(self, key: int) -> str:
        return self.layout[key // self.n_partitions % len(self.layout)]

    def record(self, type_name: str) -> Record | None:
        """The record type ``type_name``; ``None`` for a flat type."""
        return next((r for t, r in self.records if t == type_name), None)

    def bound(self, key: int) -> tuple:
        return (key, self.type_of(key), BUCKET)

    def load_values(self, seed: int) -> Load:
        rng = rng_for(seed, 0)
        incs = rng.integers(1, 1000, size=self.n_keys)
        masks = rng.integers(1, 16, size=self.n_keys)
        return Load(incs, masks, int(seed))

    def load_update(self, key: int, load: Load) -> tuple:
        """The load's write of ``key``: a counter incremented, a set
        given one to four elements, a record inserted as YCSB inserts
        one, every field in one update."""
        t = self.type_of(key)
        if t == "counter_pn":
            return (self.bound(key), "increment", int(load.incs[key]))
        if t == "set_aw":
            m = int(load.masks[key])
            return (self.bound(key), "add_all",
                    [e for i, e in enumerate(ELEMS) if m >> i & 1])
        rec = self.record(t)
        return (self.bound(key), "update", [
            (f, ("assign", field_value(load.seed, key, f[0],
                                       rec.field_bytes)))
            for f in rec.fields])


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
        t = self.ks.type_of(key)
        if t == "counter_pn":
            op = "increment" if self.rng.random() < 0.7 else "decrement"
            return (key, op, int(self.rng.integers(1, 100)))
        if t == "set_aw":
            op = "add" if self.rng.random() < 0.5 else "remove"
            return (key, op, ELEMS[int(self.rng.integers(len(ELEMS)))])
        # a record: one field of its own, chosen alike, a fresh value
        # (YCSB's update with writeallfields=false)
        rec = self.ks.record(t)
        f = rec.fields[int(self.rng.integers(len(rec.fields)))]
        return (key, "update",
                [(f, ("assign", self.rng.bytes(rec.field_bytes)))])

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
