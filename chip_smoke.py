#!/usr/bin/env python3
"""chip_smoke.py — start the served database on the chip and check what
it answers.  The quickest proof that the system still comes up on a TPU.

One process, normal entry points only (``AntidoteTPU``, ``PbServer`` /
``PbClient``, ``DataCenter`` + ``TcpTransport``), default ``Config``
apart from sizes and ``data_dir``.  Data comes from ``--seed``; every
answer is compared with :class:`PlainStore`, a dict of Python ints and
sets fed the same operations — not with ``crdt/``, the materializer or
the log.

  A  load 131,072 keys per partition (3 of 4 ``counter_pn``, 1 of 4
     ``set_aw``) through ``api.py``, a Zipfian(0.99) second write round,
     then static reads and interactive read-modify-write transactions
     over the wire protocol, each acknowledged write read back at its
     commit clock
  B  close, reopen from the same ``data_dir``, re-read a seeded sample
  C  two DCs in this process over real TCP: write at dc1 (counters,
     sets and a few RGA documents), read at dc2 at dc1's commit clocks
  D  the Pallas full-shard read at the headline shape (K = 1M, int32),
     compiled by Mosaic, bit-equal to the jnp read

The size is not a taste: the per-partition value cache in front of the
planes holds 65,536 entries (txn/manager.py ``_val_cache_cap``) and is
seeded by the writes themselves, so below that the device serves no read
at all.  131,072 keys per partition is twice the cap.  Where HBM or the
time limit forces a cut, partitions go (16 -> 8 -> 4), never keys per
partition, and the cut is printed under ``reduced``: the default ring is
4 partitions, the reference's test ring.

On a host with several chips the default ``Config`` shards every plane
over ``Mesh(part:n)``; the smoke then runs phase A a second time under
ring placement and checks where the state lives.

Exit status 0 and a last stdout line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``
only if every phase passed, the chip did the work, and nothing under
``antidote_tpu.*`` logged an ERROR.  Without a TPU it exits 2 before
touching the repo; it never sets ``JAX_PLATFORMS`` and never runs on
the CPU.
"""

from __future__ import annotations

import argparse
import gc
import json
import logging
import os
import shutil
import sys
import tempfile
import threading
import time
import traceback
from dataclasses import asdict, dataclass, replace

import numpy as np

#: the reference's production ring (config/vars.config:5)
FULL_PARTITIONS = 16
#: what fits the 1200 s limit, compilation included: a cold run at 4
#: partitions takes about 720 s on a v5e's host (390 s of it phase D's
#: one-off compiles at K = 1M), and phases A and B grow with the
#: keyspace, so 8 partitions would need about 1,000 s and 16 about
#: 1,600 s.  HBM is not the limit (about 2.4 KB per resident key: 16
#: partitions would take 5 of the chip's 16 GB).  Measured in PR 21,
#: see PERF.md.
DEFAULT_PARTITIONS = 4
#: twice the value-cache cap (txn/manager.py _val_cache_cap = 65,536)
KEYS_PER_PARTITION = 131_072
ELEMS = tuple(b"e%d" % i for i in range(6))
BUCKET = "smoke"


@dataclass
class Sizes:
    """How much the run does.  The defaults are the contract; the unit
    test (CPU, seconds) passes small ones."""

    partitions: int = DEFAULT_PARTITIONS
    keys_per_partition: int = KEYS_PER_PARTITION
    #: static reads of 10 keys, and interactive transactions, over TCP
    reads: int = 2000
    rmw: int = 500
    #: phase C: transactions at dc1, over this many keys
    dc_txns: int = 2000
    dc_keys: int = 8192
    phases: str = "ABCD"


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


# ------------------------------------------------------------ the reference


class PlainStore:
    """The plain reference: Python ints, sets and lists, one entry per
    key, fed the same operations in the same order.  Single writer, so
    an add-wins set is a set and a replicated sequence is a list."""

    def __init__(self):
        self.data: dict = {}

    def apply(self, update) -> None:
        (key, type_name, _bucket), op, arg = update
        if type_name == "counter_pn":
            sign = 1 if op == "increment" else -1
            self.data[key] = self.data.get(key, 0) + sign * arg
            return
        if type_name == "rga":  # add_right (after, elem) / remove at
            text = self.data.setdefault(key, [])
            if op == "add_right":
                text.insert(*arg)
            else:
                del text[arg - 1]
            return
        elems = self.data.setdefault(key, set())
        if op in ("add", "add_all"):
            elems.update(arg if op == "add_all" else (arg,))
        else:
            elems.difference_update(arg if op == "remove_all" else (arg,))

    def value(self, key, type_name: str):
        """What a read of the key must return."""
        if type_name == "counter_pn":
            return self.data.get(key, 0)
        if type_name == "rga":
            return list(self.data.get(key, ()))
        return sorted(self.data.get(key, ()))


# ------------------------------------------------------------- the workload


class Workload:
    """Keys, and the seeded operation streams over them.  Integer keys
    place by modulo (txn/node.py partition_index), so partition p holds
    exactly ``keys_per_partition`` keys; a key's type follows its row
    within the partition so every partition holds both types."""

    def __init__(self, seed: int, n_partitions: int,
                 keys_per_partition: int):
        self.rng = np.random.default_rng(seed)
        self.n_partitions = n_partitions
        self.n_keys = n_partitions * keys_per_partition
        #: Zipfian(0.99) rank -> key, through a seeded permutation so
        #: the hot keys spread over partitions and types
        self._perm = self.rng.permutation(self.n_keys)
        w = np.arange(1, self.n_keys + 1, dtype=np.float64) ** -0.99
        self._cdf = np.cumsum(w) / w.sum()

    def type_of(self, key) -> str:
        if isinstance(key, str):
            return "rga"  # phase C's sequence documents
        return ("set_aw" if (key // self.n_partitions) % 4 == 3
                else "counter_pn")

    def bound(self, key) -> tuple:
        return (key, self.type_of(key), BUCKET)

    def uniform_keys(self, n: int) -> list:
        return [int(k) for k in self.rng.integers(0, self.n_keys, size=n)]

    def zipf_keys(self, n: int) -> list:
        ranks = np.searchsorted(self._cdf, self.rng.random(n))
        return [int(k) for k in self._perm[ranks]]

    def load_batches(self, per_txn: int):
        """Every key written once: a counter incremented, a set given
        one to four elements."""
        incs = self.rng.integers(1, 1000, size=self.n_keys)
        masks = self.rng.integers(1, 1 << len(ELEMS), size=self.n_keys)
        for lo in range(0, self.n_keys, per_txn):
            batch = []
            for key in range(lo, min(lo + per_txn, self.n_keys)):
                if self.type_of(key) == "counter_pn":
                    batch.append((self.bound(key), "increment",
                                  int(incs[key])))
                else:
                    m = int(masks[key]) & 0b1111 or 1
                    batch.append((self.bound(key), "add_all", [
                        e for i, e in enumerate(ELEMS) if m >> i & 1]))
            yield batch

    def mutation(self, key, ref: PlainStore) -> tuple:
        """One more write to ``key``: counters move either way; a set
        loses an element it holds (so a fold has dots to cancel) or
        gains one it lacks; a document is edited."""
        if self.type_of(key) == "counter_pn":
            op = "increment" if self.rng.random() < 0.7 else "decrement"
            return (self.bound(key), op, int(self.rng.integers(1, 100)))
        if self.type_of(key) == "rga":
            # a sequence document: mostly inserts, some deletes
            n = len(ref.data.get(key, ()))
            if n and self.rng.random() < 0.3:
                return (self.bound(key), "remove",
                        int(self.rng.integers(1, n + 1)))
            return (self.bound(key), "add_right",
                    (int(self.rng.integers(0, n + 1)),
                     int(self.rng.integers(256))))
        held = sorted(ref.data.get(key, ()))
        if held and (len(held) == len(ELEMS) or self.rng.random() < 0.5):
            return (self.bound(key), "remove",
                    held[int(self.rng.integers(len(held)))])
        lacking = [e for e in ELEMS if e not in held]
        return (self.bound(key), "add",
                lacking[int(self.rng.integers(len(lacking)))])


# ------------------------------------------------------------- the watchers


class LogWatch(logging.Handler):
    """Every WARNING-or-worse record under ``antidote_tpu.*`` and every
    exception that kills a thread.  The fused-read fall-backs
    (mat/serve.py, txn/manager.py) and the background flusher announce
    a swallowed failure with ``log.exception`` and carry on — here any
    such record fails the run."""

    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.records: list = []
        self.thread_errors: list = []

    def emit(self, record: logging.LogRecord) -> None:
        self.records.append(record)
        print(f"[smoke] log {record.levelname} {record.name}: "
              f"{record.getMessage()[:400]}", file=sys.stderr, flush=True)
        if record.exc_info:
            print("".join(traceback.format_exception(
                *record.exc_info))[-2000:], file=sys.stderr, flush=True)

    def __enter__(self):
        logging.getLogger("antidote_tpu").addHandler(self)
        self._prev_hook = threading.excepthook

        def hook(args):
            self.thread_errors.append(
                f"{args.thread.name if args.thread else '?'}: "
                f"{args.exc_type.__name__}: {args.exc_value}")
            self._prev_hook(args)

        threading.excepthook = hook
        return self

    def __exit__(self, *exc):
        threading.excepthook = self._prev_hook
        logging.getLogger("antidote_tpu").removeHandler(self)
        return False

    def warnings(self) -> int:
        return sum(r.levelno < logging.ERROR for r in self.records)

    def check(self, where: str) -> None:
        errors = [f"{r.name}: {r.getMessage()[:300]}"
                  for r in self.records if r.levelno >= logging.ERROR]
        require(not errors, f"{where}: {len(errors)} ERROR log "
                            f"record(s), first: {errors[:1]}")
        require(not self.thread_errors,
                f"{where}: thread died: {self.thread_errors[:3]}")


class CompileWatch:
    """Seconds JAX spent compiling (or loading from the persistent
    cache) per jitted function, and persistent-cache hits, from JAX's
    own monitoring events."""

    _DURATION = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring as mon

        self.by_fun: dict = {}
        self.programs = 0
        self.cache_hits = 0
        self._lock = threading.Lock()
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw) -> None:
        if event == self._DURATION:
            with self._lock:
                name = str(kw.get("fun_name", "?"))
                self.by_fun[name] = self.by_fun.get(name, 0.0) + duration
                self.programs += 1

    def _on_event(self, event, **kw) -> None:
        if event == self._HIT:
            with self._lock:
                self.cache_hits += 1

    def seconds(self) -> float:
        with self._lock:
            return sum(self.by_fun.values())

    def slowest(self, n: int = 8) -> list:
        with self._lock:
            top = sorted(self.by_fun.items(), key=lambda kv: -kv[1])[:n]
        return [(name, round(s, 2)) for name, s in top]


def device_memory() -> list:
    """Per device: bytes in use, peak, limit, as the runtime reports
    them (None where the backend keeps no such statistics)."""
    import jax

    out = []
    for d in jax.devices():
        ms = d.memory_stats() or {}
        out.append({k: ms.get(k) for k in
                    ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")})
    return out


def in_use(mem: list) -> int:
    return sum(m["bytes_in_use"] or 0 for m in mem)


def mib(mem: list) -> str:
    return "HBM MiB in use/peak per device " + ", ".join(
        f"{(m['bytes_in_use'] or 0) >> 20}/"
        f"{(m['peak_bytes_in_use'] or 0) >> 20}" for m in mem)


def counters() -> dict:
    """The process-wide counts the proof rests on."""
    from antidote_tpu import stats
    from antidote_tpu.mat import ingest
    from antidote_tpu.obs.prof import profiler

    reg = stats.registry
    kernels = profiler.snapshot()["kernels"]
    return {
        "read_dispatches": int(reg.read_dispatches.value()),
        "read_cache_hits": int(reg.read_cache_hits.value()),
        "read_cache_misses": int(reg.read_cache_misses.value()),
        "device_flushes": int(sum(
            reg.ingest_flushes.value(kind=k)
            for k in ingest.INGEST_FLUSH_KINDS)),
        "gc_folds": sum(k["calls"] for name, k in kernels.items()
                        if name.endswith("_gc")),
        "kernel_compile_misses": sum(
            k["compile_misses"] for k in kernels.values()),
    }


def delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


def plane_report(db) -> dict:
    """Where every plane's state lives, and how many keys are
    device-resident against host-held."""
    import jax

    platforms: set = set()
    per_partition = []
    resident = host = 0
    for pm in db.node.partitions:
        devs: set = set()
        for plane in pm.device.planes.values():
            resident += len(getattr(plane, "key_index", ()))
            # map and RGA planes keep their arrays in sub-planes and
            # per-document states; the flat and slotted planes in .st
            for leaf in jax.tree_util.tree_leaves(
                    getattr(plane, "st", None)):
                devs.update(leaf.devices())
        host += len(pm.device.host_only)
        platforms.update(d.platform for d in devs)
        per_partition.append(sorted(d.id for d in devs))
    return {
        "platforms": sorted(platforms),
        "devices_per_partition": per_partition,
        "resident_keys": resident,
        "host_only_keys": host,
        "resident_share": round(resident / max(resident + host, 1), 6),
    }


# ------------------------------------------------------------------ phases


class Smoke:
    """The phases' shared state: the workload, the reference, the data
    directory, and the last acknowledged commit clock."""

    def __init__(self, seed: int, sizes: Sizes, workdir: str,
                 overrides: dict | None = None):
        self.sizes = sizes
        self.workload = Workload(seed, sizes.partitions,
                                 sizes.keys_per_partition)
        self.ref = PlainStore()
        self.workdir = workdir
        self.data_dir = os.path.join(workdir, "dc")
        #: Config fields set besides sizes and data_dir (the ring leg)
        self.overrides = dict(overrides or {})
        self.clock = None
        #: keys written over the wire — phase B re-reads every one
        self.served_writes: list = []

    def open_db(self):
        from antidote_tpu.api import AntidoteTPU
        from antidote_tpu.config import Config

        # a closed node is cyclic garbage that still holds its device
        # buffers: collect it, so the memory read next is this node's
        gc.collect()
        return AntidoteTPU(config=Config(
            n_partitions=self.sizes.partitions, data_dir=self.data_dir,
            **self.overrides))

    def _commit(self, db, batch: list) -> None:
        self.clock = db.update_objects_static(self.clock, batch)
        for update in batch:  # acknowledged: now the reference has it
            self.ref.apply(update)

    def _check_read(self, read, keys: list, where: str) -> None:
        wl = self.workload
        values, _clock = read(self.clock, [wl.bound(k) for k in keys])
        for key, got in zip(keys, values):
            want = self.ref.value(key, wl.type_of(key))
            require(got == want,
                    f"{where}: key {key} ({wl.type_of(key)}) read "
                    f"{got!r}, the plain reference holds {want!r}")

    # -- A: load and serve, one DC -------------------------------------

    def phase_a(self, load_txn: int = 1024, round2_txn: int = 128) -> dict:
        from antidote_tpu.pb.client import PbClient
        from antidote_tpu.pb.server import PbServer

        wl = self.workload
        n_reads, n_rmw = self.sizes.reads, self.sizes.rmw
        out: dict = {"keys": wl.n_keys,
                     "partitions": self.sizes.partitions}
        t0 = time.perf_counter()
        db = self.open_db()
        out["memory_empty_node"] = device_memory()
        c0 = counters()
        try:
            for batch in wl.load_batches(load_txn):
                self._commit(db, batch)
            out["load_s"] = round(time.perf_counter() - t0, 1)
            out["memory_after_load"] = device_memory()
            say(f"A: loaded {wl.n_keys} keys in {out['load_s']} s, "
                f"{mib(out['memory_after_load'])} "
                f"(empty node {mib(out['memory_empty_node'])})")

            t1 = time.perf_counter()
            hot = wl.zipf_keys(wl.n_keys // 10)
            for lo in range(0, len(hot), round2_txn):
                batch = []
                for key in hot[lo:lo + round2_txn]:
                    update = wl.mutation(key, self.ref)
                    batch.append(update)
                    # a later write in this transaction sees this one
                    self.ref.apply(update)
                self.clock = db.update_objects_static(self.clock, batch)
            out["round2_s"] = round(time.perf_counter() - t1, 1)
            out["writes"] = delta(counters(), c0)
            say(f"A: {len(hot)} Zipfian writes in {out['round2_s']} s, "
                f"{out['writes']}")

            t2 = time.perf_counter()
            c1 = counters()
            server = PbServer(db, port=0).start()
            try:
                with PbClient(port=server.port, timeout=300.0) as cl:
                    self._serve(cl, n_reads, n_rmw)
            finally:
                server.stop()
            out["serve_s"] = round(time.perf_counter() - t2, 1)
            out["reads"] = delta(counters(), c1)
            out["reads"].update(static_reads=n_reads, rmw_txns=n_rmw)
            say(f"A: served {n_reads} static reads and {n_rmw} "
                f"read-modify-write transactions over TCP in "
                f"{out['serve_s']} s, {out['reads']}")
            out["planes"] = plane_report(db)
            out["memory_after_serve"] = device_memory()
            out["stable_pair_equal"] = self._stable_pair_equal(db)
        finally:
            db.close()
        out["wall_s"] = round(time.perf_counter() - t0, 1)
        return out

    def _serve(self, cl, n_reads: int, n_rmw: int) -> None:
        wl = self.workload
        for _ in range(n_reads):
            # 8 keys anywhere (mostly never read, half of them outside
            # the value cache) and 2 hot ones (rewritten many times,
            # some spilled to the host path)
            self._check_read(cl.read_objects_static,
                             wl.uniform_keys(8) + wl.zipf_keys(2),
                             "A static read")
        for _ in range(n_rmw):
            keys = list(dict.fromkeys(
                wl.uniform_keys(2) + wl.zipf_keys(1)))
            tx = cl.start_transaction(self.clock)
            got = cl.read_objects([wl.bound(k) for k in keys], tx)
            for key, value in zip(keys, got):
                want = self.ref.value(key, wl.type_of(key))
                require(value == want,
                        f"A transaction read: key {key} read {value!r}, "
                        f"the plain reference holds {want!r}")
            updates = [wl.mutation(k, self.ref) for k in keys]
            cl.update_objects(updates, tx)
            self.clock = cl.commit_transaction(tx)
            for update in updates:
                self.ref.apply(update)
            self.served_writes.extend(keys)
            # the acknowledged write, read back at its commit clock
            self._check_read(cl.read_objects_static, keys,
                             "A read-back at the commit clock")

    @staticmethod
    def _stable_pair_equal(db):
        """Ring placement over several chips folds the stable snapshot
        on the devices: it must equal the host oracle.  None where the
        node runs no such tracker."""
        trk = db.node.stable_tracker
        if trk is None:
            return None
        dev, host = trk.snapshot_pair()
        return dict(dev) == dict(host)

    # -- B: restart ----------------------------------------------------

    def phase_b(self, n_sample: int = 4000) -> dict:
        wl = self.workload
        out: dict = {}
        t0 = time.perf_counter()
        c0 = counters()
        db = self.open_db()  # Config.recover_from_log: checkpoint + log
        out["recover_s"] = round(time.perf_counter() - t0, 1)
        try:
            sample = list(dict.fromkeys(
                self.served_writes + wl.zipf_keys(n_sample // 4)
                + wl.uniform_keys(n_sample)))
            for lo in range(0, len(sample), 100):
                self._check_read(db.read_objects_static,
                                 sample[lo:lo + 100], "B after restart")
            # and it still takes writes
            keys = list(dict.fromkeys(wl.uniform_keys(64)))
            batch = []
            for key in keys:
                update = wl.mutation(key, self.ref)
                batch.append(update)
                self.ref.apply(update)
            self.clock = db.update_objects_static(self.clock, batch)
            self._check_read(db.read_objects_static, keys,
                             "B write after restart")
            out["sample"] = len(sample)
            out["planes"] = plane_report(db)
            out["counters"] = delta(counters(), c0)
        finally:
            db.close()
        out["wall_s"] = round(time.perf_counter() - t0, 1)
        say(f"B: recovered in {out['recover_s']} s, {out['sample']} "
            f"sampled keys equal the reference, {out['counters']}")
        return out

    # -- C: two DCs ------------------------------------------------------

    def phase_c(self, per_round: int = 200) -> dict:
        from antidote_tpu.config import Config
        from antidote_tpu.interdc.dc import DataCenter, connect_dcs
        from antidote_tpu.interdc.tcp import TcpTransport

        n_txns = self.sizes.dc_txns
        wl = Workload(int(self.workload.rng.integers(1 << 31)), 4,
                      self.sizes.dc_keys // 4)
        ref = PlainStore()
        t0 = time.perf_counter()
        dcs = []
        try:
            for name in ("dc1", "dc2"):
                dcs.append(DataCenter(
                    name, TcpTransport(),
                    config=Config(n_partitions=4),
                    data_dir=os.path.join(self.workdir, name)))
            connect_dcs(dcs)
            for dc in dcs:
                dc.start_bg_processes()
            dc1, dc2 = dcs
            clock = None
            done = 0
            while done < n_txns:
                touched: dict = {}
                for _ in range(min(per_round, n_txns - done)):
                    batch = []
                    # three keys anywhere, a hot one, and in every
                    # fourth transaction one of eight sequence documents
                    # (the RGA planes sort and scatter int64 clocks:
                    # they have to compile too)
                    keys = wl.uniform_keys(3) + wl.zipf_keys(1)
                    if done % 4 == 0:
                        keys.append(f"doc{done // 4 % 8}")
                    for key in keys:
                        update = wl.mutation(key, ref)
                        batch.append(update)
                        ref.apply(update)
                        touched[key] = None
                    clock = dc1.update_objects_static(clock, batch)
                    done += 1
                # dc1 is quiet now: dc2 at dc1's commit clock must hold
                # exactly what dc1 acknowledged
                keys = list(touched)
                for lo in range(0, len(keys), 100):
                    part = keys[lo:lo + 100]
                    values, _ = dc2.read_objects_static(
                        clock, [wl.bound(k) for k in part])
                    for key, got in zip(part, values):
                        want = ref.value(key, wl.type_of(key))
                        require(got == want,
                                f"C: dc2 at dc1's commit clock reads "
                                f"key {key} as {got!r}, dc1 acknowledged "
                                f"{want!r}")
            planes = plane_report(dc2)
        finally:
            for dc in dcs:
                dc.close()
                dc.bus.close()
        out = {"txns": n_txns, "dc2_planes": planes,
               "wall_s": round(time.perf_counter() - t0, 1)}
        say(f"C: {n_txns} transactions at dc1 read back at dc2 over "
            f"TCP in {out['wall_s']} s")
        return out


# -- D: the kernel that is Pallas ------------------------------------------


def phase_d() -> dict:
    """``__graft_entry__.entry()`` and the fused full-shard read at the
    headline shape.  Nothing here names interpret mode, and store.py
    does not select it: what runs was compiled by Mosaic, or raised."""
    import jax
    import jax.numpy as jnp

    import __graft_entry__ as graft
    from antidote_tpu.mat import store
    from bench import HEADLINE_SHAPE, build_stream

    t0 = time.perf_counter()
    fn, args = graft.entry()
    _st, present = fn(*args)
    present = np.asarray(present)
    require(present.dtype == np.bool_ and present.any(),
            "D: entry() returned no presence")

    K, B, D, n_dcs = (HEADLINE_SHAPE[k] for k in ("K", "B", "D", "n_dcs"))
    steps = build_stream(K, B, 6, D, n_dcs, np.random.default_rng(0))
    st = store.orset_shard_init(K, n_lanes=8, n_slots=8, n_dcs=D,
                                dtype=jnp.int32)
    for i, s in enumerate(steps):
        st, _overflow = store.orset_append(
            st, *(jnp.asarray(s[f]) for f in (
                "key_idx", "lane_off", "elem_slot", "is_add", "dot_dc",
                "dot_seq", "obs_vv", "op_dc", "op_ct", "op_ss")))
        if i == 3:  # a folded base under live lanes
            st = store.orset_gc(st, jnp.asarray(s["frontier"]))
    frontier = jnp.asarray(steps[-1]["frontier"])
    memory = device_memory()
    got = np.asarray(store.orset_read_full(st, frontier, fused=True))
    want = np.asarray(store.orset_read(st, frontier))
    require(got.shape == (K, 8) and (got == want).all(),
            f"D: fused read differs from orset_read in "
            f"{int((got != want).sum())} of {got.size} cells")
    require(bool(want.any()) and not bool(want.all()),
            "D: the reference presence is trivial")
    block_k = sorted(set(store.BLOCK_K_CHOSEN.values()))
    hlo = jax.jit(lambda s_, vc: store.orset_read_full(
        s_, vc, fused=True, block_k=block_k[0])).lower(
            st, frontier).as_text()
    out = {"K": K, "present": int(want.sum()), "block_k": block_k,
           "mosaic": "tpu_custom_call" in hlo,
           "memory_with_shard": memory,
           "wall_s": round(time.perf_counter() - t0, 1)}
    say(f"D: fused Pallas read at K={K} bit-equal to orset_read, "
        f"block_k {block_k}, mosaic={out['mosaic']}, {out['wall_s']} s")
    return out


# ----------------------------------------------------------------- the run


def native_libraries() -> dict:
    """Which native libraries this checkout builds and loads (the node
    takes them through the same call; ``"auto"`` falls back to Python
    when one is missing).  With g++ on PATH each has to build."""
    import ctypes

    from antidote_tpu.native.build import ensure_built

    loaded = {}
    for name in ("oplog", "fabric"):
        path = ensure_built(name)
        loaded[name] = path is not None and bool(ctypes.CDLL(path))
    if shutil.which("g++"):
        missing = [n for n, ok in loaded.items() if not ok]
        require(not missing, f"g++ is on PATH but {missing} did not build")
    return loaded


def chip_proof(a: dict, placement: str) -> None:
    """Fail unless the devices JAX reports did phase A's work."""
    import jax

    devs = jax.devices()
    planes = a["planes"]
    require(planes["platforms"] == [devs[0].platform],
            f"plane state lives on {planes['platforms']}, not on "
            f"{devs[0].platform}")
    reads = a["reads"]
    require(reads["read_dispatches"] > 0 and reads["read_cache_misses"] > 0,
            f"the checked reads never reached the device: {reads}")
    writes = a["writes"]
    require(writes["device_flushes"] > 0 and writes["gc_folds"] > 0,
            f"no device flush or no GC fold ran: {writes}")
    require(planes["resident_keys"] > 0, "no key is device-resident")
    if len(devs) == 1:
        return
    per_dev = [m["bytes_in_use"] for m in a["memory_after_serve"]]
    require(all(per_dev) and max(per_dev) < sum(per_dev),
            f"state is not spread over the devices: {per_dev}")
    where = planes["devices_per_partition"]
    if placement == "ring":
        want = [[devs[p % len(devs)].id] for p in range(len(where))]
        require(where == want,
                f"ring placement put the partitions on {where}")
        require(a["stable_pair_equal"] is True,
                "the device-folded stable snapshot differs from the "
                "host oracle")
    else:
        require(all(len(d) == len(devs) for d in where),
                f"sharded planes do not span the mesh: {where}")


def run_phases(seed: int, sizes: Sizes, workdir: str, watch: LogWatch,
               proof=None) -> dict:
    """The phases in order, the log checked after each.  No phase is
    wrapped in a try: the first failure ends the run.  ``proof(a,
    placement)`` judges a finished phase A before more time is spent
    (the chip run passes :func:`chip_proof`)."""
    import jax

    n_dev = len(jax.devices())
    result: dict = {}
    smoke = Smoke(seed, sizes, workdir)
    if "A" in sizes.phases:
        a = result["A"] = smoke.phase_a()
        watch.check("A")
        if proof is not None:
            proof(a, "sharded" if n_dev > 1 else "one")
    if "B" in sizes.phases:
        require("A" in sizes.phases, "phase B restarts phase A's node")
        result["B"] = smoke.phase_b()
        watch.check("B")
    if "A" in sizes.phases and n_dev > 1 and proof is not None:
        # several chips: the default Config sharded every plane over
        # the mesh above; the other way to use them pins partition p
        # to chip p % n — one partition per chip, a quarter of the
        # full ring
        ring = Smoke(seed + 1, replace(sizes, partitions=n_dev),
                     os.path.join(workdir, "ring"),
                     overrides={"device_placement": "ring",
                                "mat_sharded": False})
        r = result["A_ring"] = ring.phase_a()
        watch.check("A under ring placement")
        proof(r, "ring")
    if "C" in sizes.phases:
        result["C"] = smoke.phase_c()
        watch.check("C")
    del smoke
    gc.collect()
    result["memory_nodes_closed"] = device_memory()
    if "D" in sizes.phases:
        d = result["D"] = phase_d()
        watch.check("D")
        require(d["mosaic"], "D: no Mosaic kernel in the program")
    return result


def run(seed: int, sizes: Sizes, device: dict, cache_dir: str) -> dict:
    """The whole smoke on the chip; returns the result record."""
    keys = sizes.partitions * sizes.keys_per_partition
    result: dict = {
        "device": device, "seed": seed, "phases": sizes.phases,
        "partitions": sizes.partitions,
        "keys_per_partition": sizes.keys_per_partition, "keys": keys,
        "reduced": ([] if sizes.partitions >= FULL_PARTITIONS else [
            f"partitions {FULL_PARTITIONS} -> {sizes.partitions} (the "
            "1200 s limit: cold, 4 partitions take ~720 s on a v5e's "
            "host; HBM is not the limit; keys per partition kept)"]),
        "cache_dir": cache_dir,
    }
    t0 = time.perf_counter()
    compiles = CompileWatch()
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        with LogWatch() as watch:
            result["native"] = native_libraries()
            result.update(run_phases(seed, sizes, workdir, watch,
                                     proof=chip_proof))
            result["log_warnings"] = watch.warnings()
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    if "A" in result:
        a = result["A"]
        result["bytes_per_resident_key"] = round(
            (in_use(a["memory_after_load"])
             - in_use(a["memory_empty_node"]))
            / a["planes"]["resident_keys"], 1)
    result["compile"] = {
        "programs": compiles.programs,
        "seconds": round(compiles.seconds(), 1),
        "persistent_cache_hits": compiles.cache_hits,
        "slowest": compiles.slowest(),
    }
    result["wall_s"] = round(time.perf_counter() - t0, 1)
    return result


def cold_and_warm(cache_dir: str, result: dict) -> dict:
    """This run beside earlier runs of the same size against the same
    cache directory: the first was cold and filled the cache, the
    latest is warm if it is not the first."""
    path = os.path.join(cache_dir, "chip_smoke_runs.json")
    try:
        with open(path) as f:
            runs = json.load(f)
    except (OSError, ValueError):
        runs = []
    runs.append({"keys": result["keys"], "phases": result["phases"],
                 "wall_s": result["wall_s"],
                 "compile_s": result["compile"]["seconds"],
                 "programs": result["compile"]["programs"],
                 "cache_hits": result["compile"]["persistent_cache_hits"]})
    os.makedirs(cache_dir, exist_ok=True)
    with open(path, "w") as f:
        json.dump(runs[-16:], f)
    same = [r for r in runs if (r["keys"], r["phases"])
            == (result["keys"], result["phases"])]
    return {"cold": same[0], "warm": same[-1] if len(same) > 1 else None}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    for name, value in asdict(Sizes()).items():
        ap.add_argument("--" + name.replace("_", "-"), type=type(value),
                        default=value)
    options = vars(ap.parse_args(argv))
    seed = options.pop("seed")
    sizes = Sizes(**options)

    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU (JAX's backend is "
              f"{jax.default_backend()!r}); this smoke does not run on "
              "the CPU", file=sys.stderr)
        return 2
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say(f"backend {jax.default_backend()}, device_kind "
        f"{device['kind']!r}, {device['count']} device(s)")

    from antidote_tpu.runtime import enable_compile_cache

    cache_dir = enable_compile_cache()
    result = run(seed, sizes, device, cache_dir)
    result["runs"] = cold_and_warm(cache_dir, result)
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "chip_smoke_result.json"), "w") as f:
        json.dump(result, f, indent=1)
    print("SMOKE_RESULT " + json.dumps(brief(result)), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def brief(result: dict) -> dict:
    """The result line: the record without the per-phase detail (that
    is in chiprun_out/chip_smoke_result.json)."""
    phases = ("A", "A_ring", "B", "C", "D")
    out = {k: v for k, v in result.items()
           if k not in phases and k != "memory_nodes_closed"}
    out["phase_wall_s"] = {p: result[p]["wall_s"]
                           for p in phases if p in result}
    for leg in ("A", "A_ring"):
        if leg not in result:
            continue
        a = result[leg]
        out[leg] = {
            "keys": a["keys"], "load_s": a["load_s"],
            "reads": a["reads"], "writes": a["writes"],
            "resident_share": a["planes"]["resident_share"],
            "host_only_keys": a["planes"]["host_only_keys"],
            "bytes_in_use": {
                k: [m["bytes_in_use"] for m in a["memory_" + k]]
                for k in ("empty_node", "after_load", "after_serve")},
            "peak_bytes_in_use": [m["peak_bytes_in_use"]
                                  for m in a["memory_after_serve"]],
        }
    if "D" in result:
        out["D"] = {k: result["D"][k] for k in ("K", "block_k", "mosaic")}
    return out


if __name__ == "__main__":
    sys.exit(main())
