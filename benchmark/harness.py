"""The harness: find a cell's files by name, bring the deployment up,
drive one window with client processes, and reduce what they hand back
to the result line.  Everything that belongs to one configuration, one
mix or one per-layer metric is in a file of its own; nothing here names
one.

One process (this one) holds the chip, the node and the ``PbServer``,
and traces.  A configuration with ``dcs`` 2 or more deploys that many
``DataCenter``s in this process, each with its own ``TcpTransport`` on
loopback, its own planes on the chip and its own ``PbServer``; every
write originates at the first.  The clients are children that never
open the chip (client.py).  Set-up is everything before the window opens:
imports, compile-cache loads, the load of every key, the clients'
start and a warm-up with the cell's own generator until no new program
appears.
"""

from __future__ import annotations

import gc
import importlib.util
import itertools
import json
import logging
import os
import pickle
import select
import shutil
import subprocess
import sys
import tempfile
import threading
import time
import traceback
from dataclasses import dataclass, field

import numpy as np

from benchmark import reference, trace
from benchmark.traffic import ORIGIN_DC, ClientStream, Keyspace, Mix, rng_for

HERE = os.path.dirname(os.path.abspath(__file__))
LOAD_TXN = 1024
WARM_PHASE_S = 2.0
WARM_MIN_PHASES = 2
WARM_QUIET_PHASES = 2
WARM_MAX_S = 40.0
#: reads per combination of planes in the pattern warm-up, and the
#: batched-read buckets background work (checkpoints) reaches
PATTERN_TRIES = 6
BATCH_BUCKETS = (256, 1024, 4096)
CLIENT_TIMEOUT_S = 60.0
READBACK_KEYS = 2000


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def dc_name(i: int) -> str:
    """The id of a deployment's ``i``-th DC: the origin first."""
    return ORIGIN_DC if i == 0 else f"dc{i + 1}"


class BenchError(Exception):
    """The run cannot give a result."""


# ------------------------------------------------------- the cell's files


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: Mix
    mix_file: str
    end_to_end: list
    per_layer: list
    readers: dict = field(default_factory=dict)
    config_file: str = "configuration"

    @property
    def keyspace(self) -> Keyspace:
        return Keyspace.of(int(self.config["partitions"]),
                           int(self.config["keys_per_partition"]),
                           self.config.get("types"), self.config_file)

    @property
    def dcs(self) -> int:
        """How many DCs the configuration deploys; one without ``dcs``."""
        return int(self.config.get("dcs", 1))


def _find(root: str, paths: list, *parts: str) -> str:
    for p in paths:
        cand = os.path.join(root, p, *parts)
        if os.path.exists(cand):
            return cand
    raise BenchError(f"no {os.path.join(*parts)} under any of {paths}")


def _load_reader(path: str):
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + os.path.basename(path)[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_cell(root: str, workload: str) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json`` with its
    configuration, its mix and its per-layer readers, each found by the
    name the JSON gives."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    w = cells[workload]
    paths = bench["paths"]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    mix_file = _find(root, paths, "traffic", w["traffic"] + ".json")

    def reports(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    end_to_end = [m for m in bench["end_to_end"] if reports(m)]
    e2e_names = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    readers = {m["name"]: _load_reader(_find(
        root, paths, "layer_metrics", m["name"] + ".py"))
        for m in per_layer}
    cell = Cell(name=workload, chips=int(w["chips"]), config=config,
                mix=Mix.from_file(mix_file), mix_file=mix_file,
                end_to_end=end_to_end, per_layer=per_layer,
                readers=readers, config_file=cfg_entry["file"])
    # the types and the mix meet their keyspace here: a type the harness
    # cannot load, update and judge, or a key generator that cannot draw
    # over it, refuses now, before set-up, not after the load
    ClientStream(cell.mix, cell.keyspace, 0, 0)
    if cell.mix.probers and cell.dcs < 2:
        raise BenchError(f"{mix_file}: probers read at a second DC, and "
                         f"{cfg_entry['file']} deploys {cell.dcs}")
    return cell


# ------------------------------------------------------------ the watchers


class LogWatch(logging.Handler):
    """Every ERROR record under ``antidote_tpu.*`` and every exception
    that kills a thread (after chip_smoke.py): the fused-read fall-backs
    and the background flusher announce a swallowed failure so."""

    def __init__(self):
        super().__init__(level=logging.ERROR)
        self.errors: list = []

    def emit(self, record: logging.LogRecord) -> None:
        self.errors.append(f"{record.name}: {record.getMessage()[:300]}")
        say(f"log {record.levelname} {self.errors[-1]}")
        if record.exc_info:
            say("".join(traceback.format_exception(
                *record.exc_info))[-1500:])

    def start(self) -> None:
        logging.getLogger("antidote_tpu").addHandler(self)
        self._prev_hook = threading.excepthook

        def hook(args):
            name = args.thread.name if args.thread else "?"
            self.errors.append(f"thread {name} died: {args.exc_value!r}")
            self._prev_hook(args)

        threading.excepthook = hook

    def stop(self) -> None:
        threading.excepthook = self._prev_hook
        logging.getLogger("antidote_tpu").removeHandler(self)


class CompileWatch:
    """Programs JAX compiled or loaded from its persistent cache, from
    JAX's own monitoring events (after chip_smoke.py)."""

    _DURATION = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring as mon

        self.programs = 0
        self.seconds = 0.0
        self.names: list = []
        self._lock = threading.Lock()
        mon.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event, duration, **kw) -> None:
        if event == self._DURATION:
            with self._lock:
                self.programs += 1
                self.seconds += duration
                self.names.append(str(kw.get("fun_name", "?")))


def device_record() -> dict:
    """The device as JAX reports it, with the peak on the fullest chip."""
    import jax

    devs = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") or 0
             for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}


# ----------------------------------------------------------- the deployment


class Clients:
    """The cell's client processes for one seed: the mix's ``clients``
    at the origin DC's server, then its ``probers``, each with the
    second DC's server as well."""

    def __init__(self, cell: Cell, seed: int, ports: list, workdir: str):
        self.cell, self.workdir = cell, workdir
        n = cell.mix.clients
        ks = cell.keyspace
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        self.outs, self.procs = [], []
        for c in range(n + cell.mix.probers):
            out = os.path.join(workdir, f"client{seed}_{c}")
            params = {
                "client": c, "seed": seed, "port": ports[0],
                "n_partitions": ks.n_partitions,
                "keys_per_partition": ks.keys_per_partition,
                "types": cell.config["types"],
                "mix_file": cell.mix_file, "out": out,
                "timeout_s": CLIENT_TIMEOUT_S,
            }
            if c >= n:
                params.update(remote_port=ports[1], remote_dc=dc_name(1))
            self.outs.append(out)
            self.procs.append(subprocess.Popen(
                [sys.executable, os.path.join(HERE, "client.py"),
                 json.dumps(params)],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env,
                text=True, bufsize=1))
        try:
            self._expect("ready", 120.0)
        except BaseException:
            self.close()  # leave no client behind
            raise

    def _expect(self, word: str, timeout: float) -> list:
        """One line starting with ``word`` from every client."""
        deadline = time.monotonic() + timeout
        waiting = {p.stdout.fileno(): p for p in self.procs}
        lines = []
        while waiting:
            left = deadline - time.monotonic()
            ready = select.select(list(waiting), [], [], max(left, 0))[0]
            if not ready:
                raise BenchError(
                    f"{len(waiting)} client(s) gave no {word!r} within "
                    f"{timeout:.0f} s")
            for fd in ready:
                line = waiting[fd].stdout.readline()
                if not line:
                    raise BenchError(
                        f"a client ended before {word!r} (exit "
                        f"{waiting[fd].poll()})")
                if line.startswith(word):
                    lines.append(line)
                    del waiting[fd]
        return lines

    def run(self, phase: str, t_start: float, t_end: float):
        for p in self.procs:
            p.stdin.write(f"run {phase} {t_start!r} {t_end!r}\n")
            p.stdin.flush()

    def collect(self, phase: str, t_end: float) -> list:
        """Every client's records of ``phase``, client by client; waits
        for each answer a minute past the phase's close."""
        wait = max(t_end - time.monotonic(), 0) + CLIENT_TIMEOUT_S + 15.0
        self._expect(f"done {phase} ", wait)
        per_client = []
        for out in self.outs:
            path = f"{out}.{phase}"
            with open(path, "rb") as f:
                per_client.append(pickle.load(f))
            os.unlink(path)
        return per_client

    def close(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.stdin.close()
        for p in self.procs:
            try:
                p.wait(timeout=10.0)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
            p.stdout.close()


class Deployment:
    """The cell's configuration, up and loaded, and the windows driven
    against it."""

    def __init__(self, cell: Cell, data_seed: int):
        self.cell = cell
        self.ks = cell.keyspace
        self.workdir = tempfile.mkdtemp(prefix="bench_")
        self.data_seed = data_seed
        self.load = self.ks.load_values(data_seed)
        self.history = reference.PlainHistory(self.ks, self.load)
        self.compiles = CompileWatch()
        self.watch = LogWatch()
        self.db = self.server = None
        #: every DC's node and server, the origin's first (``db`` and
        #: ``server`` are the origin's)
        self.dbs: list = []
        self.servers: list = []

    # -- up --------------------------------------------------------------

    def _node_config(self, dc: str | None = None):
        from antidote_tpu.config import Config

        obs = os.path.join(self.workdir, "obs")
        return Config(n_partitions=self.ks.n_partitions,
                      flight_recorder_dir=(obs if dc is None
                                           else os.path.join(obs, dc)),
                      **self.cell.config.get("config", {}))

    def open(self) -> None:
        from antidote_tpu.api import AntidoteTPU
        from antidote_tpu.pb.server import PbServer

        self.watch.start()
        gc.collect()
        if self.cell.dcs == 1:
            self.dbs = [AntidoteTPU(
                dc_id=ORIGIN_DC, config=self._node_config(),
                data_dir=os.path.join(self.workdir, ORIGIN_DC))]
        else:
            self._open_dcs()
        self.db = self.dbs[0]
        clock = self._load()
        for db in self.dbs[1:]:
            self._await_replica(db, clock)
        self.servers = [PbServer(db, port=0).start() for db in self.dbs]
        self.server = self.servers[0]
        for server in self.servers:
            self._warm_patterns(server.port)

    def _open_dcs(self) -> None:
        """The configuration's DCs, each on a ``TcpTransport`` of its
        own over loopback, the full mesh connected before the load
        (upstream's ``connect_cluster``), then their background
        processes: delivery, heartbeats."""
        from antidote_tpu.interdc.dc import DataCenter, connect_dcs
        from antidote_tpu.interdc.tcp import TcpTransport

        t0 = time.monotonic()
        for i in range(self.cell.dcs):
            name, bus = dc_name(i), TcpTransport()
            try:
                self.dbs.append(DataCenter(
                    name, bus, config=self._node_config(name),
                    data_dir=os.path.join(self.workdir, name)))
            except BaseException:
                bus.close()
                raise
        connect_dcs(self.dbs)
        for dc in self.dbs:
            dc.start_bg_processes()
        say(f"{len(self.dbs)} DCs up and connected in "
            f"{time.monotonic() - t0:.1f} s")

    def _await_replica(self, db, clock, timeout_s: float = 600.0) -> None:
        """Until ``db``'s stable snapshot covers the load's last commit:
        every loaded key is then readable there."""
        t0, want = time.monotonic(), clock.get_dc(ORIGIN_DC)
        while db.node.stable_vc().get_dc(ORIGIN_DC) < want:
            if time.monotonic() - t0 > timeout_s:
                raise BenchError(f"{db.node.dc_id} did not cover the "
                                 f"load within {timeout_s:.0f} s")
            time.sleep(0.05)
        say(f"{db.node.dc_id} covered the load "
            f"{time.monotonic() - t0:.1f} s after it ended")

    def _load(self):
        """Every key written once, through the API at the origin DC;
        returns the last commit clock."""
        ks, t0 = self.ks, time.monotonic()
        clock = None
        for lo in range(0, ks.n_keys, LOAD_TXN):
            clock = self.db.update_objects_static(clock, [
                ks.load_update(k, self.load)
                for k in range(lo, min(lo + LOAD_TXN, ks.n_keys))])
        say(f"loaded {ks.n_keys} keys in {time.monotonic() - t0:.1f} s")
        return clock

    def _warm_patterns(self, port: int) -> None:
        """Every read program the window can ask for, once, before it.
        A read is one program per multiset of store calls
        (device_plane.py ``fused_read``): with equal plane shapes, one
        per count of partitions touched in each type's planes (a
        record's type reads its fields' planes).  Ten uniform keys
        reach every such combination, the rare ones once in thousands
        of reads, and each first use costs 0.2-0.6 s even from the
        persistent cache (measured on one v5e chip) — so traffic alone leaves
        some for the window.  Here each combination a read of the mix's
        keys can touch is read a few times over the wire, with keys
        drawn from the seed (some are answered by the value cache,
        hence several tries); then one batched read a type per bucket
        that a checkpoint's fold of dirty keys can reach.  Each DC's
        planes keep programs of their own, so it runs against every
        DC's server."""
        from antidote_tpu.pb.client import PbClient

        ks, t0 = self.ks, time.monotonic()
        rng = rng_for(self.data_seed, 3)
        rows: dict = {t: [] for t in dict.fromkeys(ks.layout)}
        for r in range(ks.keys_per_partition):
            rows[ks.type_of(r * ks.n_partitions)].append(r)

        def keys_of(type_name: str, partition: int, n: int) -> list:
            picked = rng.choice(len(rows[type_name]),
                                min(n, len(rows[type_name])),
                                replace=False)
            return [rows[type_name][int(i)] * ks.n_partitions + partition
                    for i in picked]

        n_parts = ks.n_partitions
        mix = self.cell.mix
        reach = max(mix.num_reads, mix.num_updates if mix.probers else 0)
        reads = 0
        with PbClient(port=port, timeout=CLIENT_TIMEOUT_S) as cl:
            for counts in itertools.product(range(n_parts + 1),
                                            repeat=len(rows)):
                if not 0 < sum(counts) <= reach:
                    continue
                for _ in range(PATTERN_TRIES):
                    keys = []
                    for type_name, n in zip(rows, counts):
                        parts = rng.permutation(n_parts)
                        keys += [k for p in parts[:n] for k in
                                 keys_of(type_name, int(p), 1)]
                    cl.read_objects_static(
                        None, [ks.bound(k) for k in keys])
                    reads += 1
            for type_name in rows:
                for bucket in BATCH_BUCKETS:
                    # the bucket below ends at a quarter; ask for five
                    # eighths, since the cache answers some
                    keys = keys_of(type_name, 0, bucket * 5 // 8)
                    if len(keys) > bucket // 4:
                        cl.read_objects_static(
                            None, [ks.bound(k) for k in keys])
                        reads += 1
        say(f"pattern warm-up: {reads} reads in "
            f"{time.monotonic() - t0:.1f} s, "
            f"{self.compiles.programs} programs so far")

    # -- counters ----------------------------------------------------------

    def counters(self) -> dict:
        from antidote_tpu import stats
        from antidote_tpu.mat import ingest
        from antidote_tpu.obs.prof import profiler

        reg = stats.registry
        kernels = profiler.snapshot()["kernels"]
        out = {name: int(getattr(reg, name).value()) for name in (
            "read_dispatches", "read_cache_hits", "read_cache_misses",
            "read_serve_groups", "ingest_dispatches", "log_fsyncs",
            "log_group_records")}
        out["ingest_flushes"] = int(sum(
            reg.ingest_flushes.value(kind=k)
            for k in ingest.INGEST_FLUSH_KINDS))
        out["gc_folds"] = sum(k["calls"] for n, k in kernels.items()
                              if n.endswith("_gc"))
        out["kernel_calls"] = sum(k["calls"] for k in kernels.values())
        out["kernel_compile_misses"] = sum(
            k["compile_misses"] for k in kernels.values())
        out["jax_programs_compiled"] = self.compiles.programs
        self.kernel_misses = {n: k["compile_misses"]
                              for n, k in kernels.items()}
        if self.cell.dcs > 1:
            # the registry is the process's: every DC feeds it, and
            # only the origin ships transactions, only the others apply
            # them.  ``Histogram`` shows its sum to its exposition only
            wait = reg.depgate_wait
            out["depgate_wait_count"] = int(wait.count)
            out["depgate_wait_us"] = int(round(wait._sum * 1e6))
            out["ship_txns"] = int(reg.ship_txns.value())
            out["ship_frames"] = int(reg.ship_frames.value(kind="batch"))
        return out

    def remote_pending(self) -> int:
        """The transactions the DCs past the origin have received and
        not yet applied: their dependency gates' queues."""
        return sum(g.pending() for db in self.dbs[1:]
                   for g in db.dep_gates)

    # -- one seed ----------------------------------------------------------

    def measure(self, seed: int, seconds: float, traced: bool,
                t_process_start: float, warm_max_s: float = WARM_MAX_S
                ) -> dict:
        """Clients for ``seed``, a warm-up, one window, the read-back of
        the acknowledged writes, and the comparison.  Returns the raw
        reading; ``result_line`` makes the line of it."""
        cell = self.cell
        clients = Clients(cell, seed, [s.port for s in self.servers],
                          self.workdir)
        try:
            warm = self._warm(clients, warm_max_s)
            t_start = time.monotonic() + 0.25
            t_end = t_start + seconds
            clients.run("window", t_start, t_end)
            time.sleep(max(t_start - time.monotonic(), 0))
            c0 = self.counters()
            misses0 = self.kernel_misses
            queued = [self.remote_pending()]
            traced_slice = None
            if traced:
                traced_slice = self._trace_slice(t_start, seconds)
            time.sleep(max(t_end - time.monotonic(), 0))
            c1 = self.counters()
            queued.append(self.remote_pending())
            window = clients.collect("window", t_end)
            device = device_record()
            reference.feed(self.history, [
                r for per_client in warm + [window]
                for records in per_client for r in records])
            readback = self._read_back(seed, warm + [window])
        finally:
            clients.close()
        records = [r for per_client in window for r in per_client]
        counters = {k: c1[k] - c0[k] for k in c1}
        reading = {
            "seed": seed, "seconds": seconds, "t_start": t_start,
            "t_end": t_end, "setup_s": t_start - t_process_start,
            "records": records, "counters": counters,
            "device": device, "readback": readback,
            "error_logs": len(self.watch.errors),
            # the other DCs' received-but-unapplied transactions at the
            # window's open and close: whether they keep up
            "remote_queued": queued if cell.dcs > 1 else None,
            "warm_phases": len(warm), "trace": None,
            # what the window compiled, by name (should be nothing)
            "compiled": {
                "jax": self.compiles.names[c0["jax_programs_compiled"]:
                                           c1["jax_programs_compiled"]],
                "kernels": {n: m - misses0.get(n, 0)
                            for n, m in self.kernel_misses.items()
                            if m > misses0.get(n, 0)}},
        }
        if traced_slice is not None:
            reading["trace"] = self._reduce(traced_slice, records, device)
        return reading

    def _warm(self, clients: Clients, warm_max_s: float) -> list:
        """The cell's own traffic until no new program appears:
        ``fused_read`` keeps one program per multiset of store calls and
        JAX one per shape under it, so only the generator itself finds
        them.  Phases of the mix alternate with phases of the mix's
        reads alone: in an update-heavy mix the few reads of a phase
        would leave most read patterns for the window to compile."""
        phases, quiet = [], 0
        reads = bool(self.cell.mix.operations.get("read_only_txn"))
        t0 = time.monotonic()
        while True:
            before = self.counters()
            t_start = time.monotonic() + 0.05
            t_end = t_start + WARM_PHASE_S
            name = "warmreads" if reads and len(phases) % 2 else "warm"
            clients.run(name, t_start, t_end)
            phases.append(clients.collect(name, t_end))
            after = self.counters()
            new = sum(after[k] - before[k] for k in (
                "kernel_compile_misses", "jax_programs_compiled"))
            quiet = quiet + 1 if new == 0 else 0
            if len(phases) >= WARM_MIN_PHASES and (
                    quiet >= WARM_QUIET_PHASES
                    or time.monotonic() - t0 >= warm_max_s):
                break
        say(f"warm-up: {len(phases)} phases, "
            f"{time.monotonic() - t0:.1f} s, last phase {new} new "
            f"program(s)")
        return phases

    def _trace_slice(self, t_start: float, seconds: float) -> dict:
        slice_s = min(3.0, 0.3 * seconds)
        log_dir = os.path.join(self.workdir, "trace")
        time.sleep(max(t_start + 0.4 * seconds - time.monotonic(), 0))
        t0 = time.monotonic()
        with trace.capture(log_dir, slice_s):
            pass
        return {"log_dir": log_dir, "t0": t0, "t1": t0 + slice_s}

    def _reduce(self, sl: dict, records: list, device: dict) -> dict:
        """The trace's numbers, and the bytes the work answered in the
        slice needs: the keys read at any DC, and each acknowledged
        operation once in every DC's planes (the origin appends it,
        every other DC applies it)."""
        out = trace.reduce_xplane(trace.xplane_of(sl["log_dir"]))
        keys_read, ops = trace.rows_of_work(
            self.ks, [r for r in records
                      if r["ok"] and sl["t0"] <= r["t_done"] <= sl["t1"]],
            self.cell.dcs)
        out["needed_bytes"] = trace.needed_bytes(
            trace.plane_row_bytes(self.db), keys_read, ops)
        out["hbm_bytes_per_s"] = trace.peaks_for(
            device["kind"])["hbm_bytes_per_s"]
        return out

    def _read_back(self, seed: int, phases: list) -> dict:
        """Once the window has closed: a seeded sample of the keys this
        seed's clients wrote, with each client's last transaction in it,
        read at the newest commit clock through the same entry the
        window drove: ten keys a static read, over the wire, at every
        DC (``remote`` holds the others' counts)."""
        from antidote_tpu.clocks import VC

        written: dict = {}
        last: dict = {}
        newest = 0
        for per_client in phases:
            for records in per_client:
                for r in records:
                    if r["ok"] and r["updates"]:
                        newest = max(newest, r["commit_time"])
                        last[r["client"]] = [k for k, _o, _a
                                             in r["updates"]]
                        for k, _o, _a in r["updates"]:
                            written[k] = None
        keys = list(written)
        rng = rng_for(seed, 2)
        if len(keys) > READBACK_KEYS:
            keys = [keys[i] for i in rng.choice(
                len(keys), READBACK_KEYS, replace=False)]
        keys = list(dict.fromkeys(
            [k for ks_ in last.values() for k in ks_] + keys))
        # and as many that nobody wrote: still what the load left
        keys += [int(k) for k in rng.integers(0, self.ks.n_keys,
                                              len(keys) // 4 + 10)]
        clock = VC({ORIGIN_DC: newest}) if newest else None
        out = self._read_back_at(self.server, keys, clock)
        out["keys"] = keys
        if self.cell.dcs > 1:
            out["remote"] = [self._read_back_at(server, keys, clock)
                             for server in self.servers[1:]]
        return out

    def _read_back_at(self, server, keys: list, clock) -> dict:
        from antidote_tpu.pb.client import PbClient

        compared = wrong = 0
        first: list = []
        dc = server.db.node.dc_id
        with PbClient(port=server.port, timeout=CLIENT_TIMEOUT_S) as cl:
            for lo in range(0, len(keys), 10):
                part = keys[lo:lo + 10]
                values, snap = cl.read_objects_static(
                    clock, [self.ks.bound(k) for k in part])
                at = snap.get_dc(ORIGIN_DC)
                for key, got in zip(part, values):
                    compared += 1
                    want = self.history.at(key, at)
                    if got != want:
                        wrong += 1
                        if len(first) < 3:
                            first.append(
                                f"read-back at {dc} key {key} at {at}: "
                                f"read {got!r}, the reference holds "
                                f"{want!r}")
        return {"compared": compared, "wrong": wrong, "first": first}

    # -- down ------------------------------------------------------------

    def close(self) -> None:
        for server in self.servers:
            server.stop()
        for db in self.dbs:
            db.close()
            if self.cell.dcs > 1:
                db.bus.close()
        self.db = self.server = None
        self.dbs, self.servers = [], []
        self.watch.stop()
        shutil.rmtree(self.workdir, ignore_errors=True)


# --------------------------------------------------- reading -> result line


@dataclass
class WindowView:
    """What a per-layer reader reads."""

    counters: dict
    answered: dict
    update_ops: int
    trace: dict | None
    #: the probers' lags, acknowledgement at the origin to the answer
    #: at another DC, in seconds (none in a deployment of one DC)
    vis_lag_s: list = field(default_factory=list)
    #: the DCs that hold every acknowledged operation in their planes:
    #: the origin appends it, each other DC applies it
    dcs: int = 1


def _p95_ms(samples: list):
    return float(np.percentile(samples, 95)) * 1000.0 if samples else None


def reduce_reading(cell: Cell, reading: dict,
                   history=None) -> dict:
    """The window's records to numbers: the end-to-end metrics, the
    per-layer metrics, the counts compared and a detail record.  A
    record answered at another DC than the origin is a prober's read:
    its lag from the acknowledgement at the origin is a sample of
    ``vis_lag_p95_ms`` (and of the per-layer ``vis_lag_p50_ms``), and it
    is compared as a remote read."""
    t_start, t_end = reading["t_start"], reading["t_end"]
    records = reading["records"]
    lat = {"read_only_txn": [], "update_only_txn": []}
    #: lags from the acknowledgement at the origin to the answer at
    #: another DC
    vis = []
    answered, in_window = {}, 0
    update_ops = failed = aborts = 0
    gaps = []
    prev_done: dict = {}
    for r in records:
        c = r["client"]
        if c in prev_done:
            gaps.append(r["t_send"] - prev_done[c])
        prev_done[c] = r["t_done"]
        # a tail is the tail of all requests: one that failed waited too
        local = r.get("dc", ORIGIN_DC) == ORIGIN_DC
        if local:
            lat[r["kind"]].append(r["t_done"] - r["t_send"])
        else:
            vis.append(r["t_done"] - r["t_acked"])
        aborts += r["aborts"]
        if not r["ok"]:
            failed += 1
            if failed <= 3:
                say(f"failed: client {c} {r['kind']} after "
                    f"{r['t_done'] - r['t_send']:.3f} s: {r['error']}")
        elif r["t_done"] <= t_end:
            in_window += 1
            kind = r["kind"] if local else "remote_" + r["kind"]
            answered[kind] = answered.get(kind, 0) + 1
            update_ops += len(r["updates"])
    seconds = t_end - t_start
    end_to_end = {
        "txn_per_s": in_window / seconds,
        "read_p95_ms": _p95_ms(lat["read_only_txn"]),
        "update_p95_ms": _p95_ms(lat["update_only_txn"]),
        "vis_lag_p95_ms": _p95_ms(vis),
        "setup_s": reading["setup_s"],
    }
    view = WindowView(counters=reading["counters"], answered=answered,
                      update_ops=update_ops, trace=reading["trace"],
                      vis_lag_s=vis, dcs=cell.dcs)
    per_layer = {name: read(view) for name, read in cell.readers.items()}

    numbers = []
    if history is not None:
        local, remote = reference.by_dc(records)
        compared, wrong, first = reference.wrong_reads(history, local)
        carried, behind = reference.behind_session(local)
        numbers += [("reads_compared", compared, ">=", 1),
                    ("reads_wrong", wrong, "<=", 0),
                    ("session_clocks_sent", carried, ">=", 1),
                    ("snapshots_behind_session", behind, "<=", 0)]
        rb = reading["readback"]
        numbers += [
            ("acks_read_back", rb["compared"], ">=", 1),
            ("acks_unreadable", rb["wrong"], "<=", 0),
            ("failed", failed, "<=", 0),
            ("device_read_dispatches",
             reading["counters"]["read_dispatches"], ">=", 1),
            ("read_cache_misses",
             reading["counters"]["read_cache_misses"], ">=", 1),
            ("error_logs", reading["error_logs"], "<=", 0),
        ]
        if cell.dcs > 1:
            # every prober read at another DC, against the history at
            # that snapshot's origin entry; its snapshot may not lie
            # below the commit it was sent with; and the read-back at
            # every other DC
            r_compared, r_wrong, r_first = reference.wrong_reads(
                history, remote)
            _sent, r_behind = reference.behind_session(remote)
            numbers += [
                ("remote_reads_compared", r_compared, ">=", 1),
                ("remote_reads_wrong", r_wrong, "<=", 0),
                ("remote_snapshots_behind_commit", r_behind, "<=", 0),
                ("remote_acks_read_back",
                 sum(x["compared"] for x in rb["remote"]), ">=", 1),
                ("remote_acks_unreadable",
                 sum(x["wrong"] for x in rb["remote"]), "<=", 0),
            ]
            first += r_first + [f for x in rb["remote"] for f in x["first"]]
        for line in first + rb["first"]:
            say("differs: " + line)
    detail = {
        "seed": reading["seed"], "window_s": seconds,
        "answered": answered, "attempted": len(records),
        "read_p50_ms": (float(np.median(lat["read_only_txn"])) * 1000.0
                        if lat["read_only_txn"] else None),
        "update_p50_ms": (float(np.median(lat["update_only_txn"]))
                          * 1000.0 if lat["update_only_txn"] else None),
        "read_max_ms": max(lat["read_only_txn"], default=0.0) * 1000.0,
        "update_max_ms": max(lat["update_only_txn"], default=0.0) * 1000.0,
        "samples": {"read": len(lat["read_only_txn"]),
                    "update": len(lat["update_only_txn"])},
        "aborts_retried": aborts,
        "client_turnaround_mean_ms": (float(np.mean(gaps)) * 1000.0
                                      if gaps else None),
        "warm_phases": reading["warm_phases"],
        "compiled_in_window": reading.get("compiled"),
        "counters": reading["counters"],
    }
    if cell.dcs > 1:
        detail.update(vis_lag_samples=len(vis),
                      remote_gate_queued_open_close=reading["remote_queued"])
    return {"end_to_end": end_to_end, "per_layer": per_layer,
            "numbers": numbers, "attempted": len(records),
            "failed": failed, "detail": detail}


def result_line(cell: Cell, traced: bool, reduced: dict, device: dict,
                trace_reading: dict | None) -> dict:
    """The object the run prints last.  ``--trace 0`` carries the cell's
    end-to-end metrics, ``--trace 1`` its per-layer metrics; a metric
    whose reader found nothing to read is left out."""
    declared = cell.per_layer if traced else cell.end_to_end
    values = reduced["per_layer" if traced else "end_to_end"]
    metrics = {}
    for m in declared:
        value = values.get(m["name"])
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = dict(device)
    line = {"correct": reference.judge(reduced["numbers"]),
            "attempted": reduced["attempted"], "failed": reduced["failed"],
            "metrics": metrics, "device": device}
    if traced:
        if not trace_reading:
            raise BenchError("a traced run without a reduced trace")
        busy, window = trace_reading["busy_s"], trace_reading["window_s"]
        if not 0 < busy <= window:
            raise BenchError(f"busy_s {busy} is not within (0, window_s "
                             f"{window}]")
        device["busy_s"], device["window_s"] = busy, window
        line["breakdown"] = trace_reading["breakdown"]
    _lines, line["compared"] = reference.compared_lines(
        reduced["numbers"])
    return line
