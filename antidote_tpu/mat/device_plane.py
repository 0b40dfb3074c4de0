"""Live device data plane — the TPU shard store serving the running DC.

This is the integration layer that makes the device materializer
(antidote_tpu/mat/store.py) the system's spine instead of a benchmark
sidecar: PartitionManager routes committed effects of supported types
here (local commits, inter-DC applies, and log recovery all take the
same path), transaction reads come back from batched device folds, and
the gossiped stable snapshot (antidote_tpu/meta/gossip.py) drives the
device GC.  The modelled duty is the reference's materializer_vnode —
update/read as the running database's data plane (reference
src/materializer_vnode.erl:56-110), with the per-key gen_server walk
replaced by padded-batch appends and lattice folds.

Host-side duties (this module): interning arbitrary Python keys,
elements, and DC ids into dense indices; buffering staged effects into
padded append blocks (amortizing dispatch); and fallback policy.  A key
*evicts* to the host path — its device rows purged, its history rebuilt
into the host store by log replay — when it exceeds its element-slot or
ring-lane capacity; reads below the device base snapshot replay the log,
exactly the reference's snapshot-cache miss
(src/materializer_vnode.erl:415-419).

Correctness contract: the dense dot tables collapse each (element,
origin-DC) dot set to its max sequence, which is the ORSWOT invariant —
sound because dots are minted per-DC-monotone (txn/node.py mint_dot) and
write-write certification serializes same-key commits at a DC.  Ops
whose dots carry actors that are not DC ids (foreign tooling writing
through the log) still work: actors get their own dense columns, capped
by ``max_dcs`` before the key evicts to the host path.

Shapes are static per (capacity, bucket): append batches pad to
power-of-two buckets so XLA compiles a handful of programs, not one per
batch size.  Capacity growth (keys / element slots / DC columns) is a
rare host-side repack (store.orset_grow).
"""

from __future__ import annotations

import contextlib
import logging
import threading
import time
from dataclasses import replace as dc_replace
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh as _Mesh

from antidote_tpu import stats
from antidote_tpu.clocks import VC, ClockDomain
from antidote_tpu.obs import prof
from antidote_tpu.obs.events import recorder
from antidote_tpu.obs.spans import tracer
from antidote_tpu.runtime import COLLECTIVE_LOCK
from antidote_tpu.mat import ingest, store
from antidote_tpu.mat.materializer import Payload

log = logging.getLogger(__name__)

#: "read latest": dominates every real µs timestamp without overflowing
#: int64 arithmetic in the fold
_VC_INF = (1 << 62)

#: the ONE dispatch-bucket quantizer (powers of four, floor 64) —
#: shared with the packed ingest packer so serving flushes and warm
#: compiles can never bucket to different shapes (mat/ingest.py)
_MIN_BUCKET = ingest._MIN_BUCKET
_bucket = ingest.bucket


#: read-fold dispatch counter (tests assert the fused cross-partition
#: path issues <= n_devices programs per multi-partition read)
_read_dispatches = 0


def count_read_dispatch() -> None:
    global _read_dispatches
    _read_dispatches += 1


def read_dispatch_count() -> int:
    return _read_dispatches


#: one compiled program per CANONICALIZED combination of fused store
#: calls (entries sorted by function, so access order doesn't mint new
#: programs); jax's own cache handles per-shape specialization under
#: each entry.  Any k same-type planes share one entry regardless of
#: which partitions they are.  Bounded: a pattern explosion clears the
#: table rather than growing it forever (the jit objects are cheap to
#: rebuild; the underlying executables live in jax's own cache).
_FUSED_CACHE: Dict[tuple, Any] = {}
_FUSED_CACHE_CAP = 64


def fused_read(splits: list) -> list:
    """Run many planes' batched read folds as ONE XLA program — the
    cross-partition read for a ring-placed node: all captures must sit
    on one chip; the caller groups by ``closure.device`` (reference:
    the coordinator's async batched reads,
    src/clocksi_interactive_coord.erl:731-747, lifted from
    per-partition to per-chip).  ``splits`` are the ``closure.split``
    pairs; returns their post-processed {key: state} dicts in order."""
    # canonical order: same multiset of store calls -> same program
    order = sorted(range(len(splits)),
                   key=lambda i: splits[i][0][0].__name__)
    fns = tuple(splits[i][0][0] for i in order)
    fn = _FUSED_CACHE.get(fns)
    if fn is None:
        if len(_FUSED_CACHE) >= _FUSED_CACHE_CAP:
            _FUSED_CACHE.clear()

        def body(argss, _fns=fns):
            return tuple(f(*a) for f, a in zip(_fns, argss))

        # one kernel-span name for every fused pattern: the per-pattern
        # jits differ, but the operator-facing question ("how long do
        # fused cross-partition reads take, how often do they compile")
        # is per call site
        fn = prof.profiler.wrap(jax.jit(body), name="fused_read",
                                subsystem="mat.device_plane")
        _FUSED_CACHE[fns] = fn
    count_read_dispatch()
    # the host side of one dispatch, in two spans: enqueueing the
    # program (the thread runs) and fetching its values (the thread
    # waits for the device)
    with tracer.span("device_dispatch", "device", plane="fused",
                     folds=len(splits)):
        outs = fn(tuple(splits[i][0][1] for i in order))
    with tracer.wait_span("device_fetch", "device", plane="fused"):
        outs = jax.tree_util.tree_map(np.asarray, outs)
    results: list = [None] * len(splits)
    for pos, i in enumerate(order):
        results[i] = splits[i][1](outs[pos])
    return results


def collective_guard(dev):
    """``COLLECTIVE_LOCK`` when ``dev`` is a mesh — the dispatch
    launches a multi-chip program, and runtime.py's invariant ("every
    collective launch site takes this lock") applies — else a no-op
    context, so the single-chip paths keep their lock-free read
    concurrency.  ``dev`` is the ``closure.device`` discriminator the
    fused-read callers already group by: sharded planes publish their
    mesh there (``_many_reader``), single-chip planes a Device."""
    if isinstance(dev, _Mesh):
        return COLLECTIVE_LOCK
    return contextlib.nullcontext()


class ReadBelowBase(Exception):
    """Read snapshot does not dominate the device base — serve from log."""


def _pack_rows(rows: List[tuple], capacity: int, d: int,
               cols: tuple) -> tuple:
    """Shared append packing: pad decoded rows to a power-of-two bucket
    and split them into per-column arrays.  ``cols`` tags each row field
    after the leading key index: "s" = int64 scalar, "vv" = (col, seq)
    pair list max-merged into a dense [B, d] vector clock.  Returns
    (key_idx[B], lane_off[B], arrays) in ``cols`` order — the exact
    argument order of the matching store ``*_append``."""
    n = len(rows)
    B = _bucket(n)
    key_idx = np.full(B, capacity, dtype=np.int32)
    arrays = [np.zeros((B, d) if tag == "vv" else B, dtype=np.int64)
              for tag in cols]
    for i, row in enumerate(rows):
        key_idx[i] = row[0]
        for a, tag, v in zip(arrays, cols, row[1:]):
            if tag == "vv":
                for col, s in v:
                    a[i, col] = max(a[i, col], s)
            else:
                a[i] = v
    lane_off = np.zeros(B, dtype=np.int32)
    lane_off[:n] = store.batch_lane_offsets(key_idx[:n])
    return key_idx, lane_off, arrays


#: (append_fn, state-shape signature, bucket) combos already compiled
#: (or being compiled) in this process — plane instances share XLA
#: programs class-wide, so one warm pass covers every partition
_WARMED: set = set()
_WARM_LOCK = threading.Lock()


def _start_warm(run, name: str) -> None:
    """Run a warm compile off the serving threads.  NOT a daemon
    thread: one force-unwound mid-XLA-call at interpreter exit aborts
    the process ("terminate called ... FATAL: exception not rethrown"),
    and on the chip a compile at deployment shapes outlasts any fixed
    grace period — so the interpreter waits for in-flight warms, which
    are finite (one compile and one run on a copy)."""
    threading.Thread(target=run, name=name).start()


class _Flight:
    """One routine flush taken out of the partition lock
    (``PartitionManager.flush_scheduled``): the rows it carries and
    their keys, which every reader treats as pending until it settles,
    and how far it got.  ``donating``: the plane's state is the donated
    argument of a dispatch in progress — or, on the overflow path, of
    the retry about to run — so nothing may capture it.  ``outs`` and
    ``dispatched`` are written under the plane's ``_flight_lock``,
    ``overflow`` by whoever fetches the mask first, ``settled`` under
    the partition lock."""

    __slots__ = ("rows", "keys", "capacity", "d", "stable", "ring_bound",
                 "donating", "outs", "dispatched", "overflow", "error",
                 "settled", "t0")

    def __init__(self, rows, keys, capacity: int, d: int,
                 stable: Optional[VC], ring_bound: VC):
        self.rows, self.keys = rows, keys
        #: the widths the rows were decoded at: a grow lands the
        #: flight first (_join_flight), so they hold until it settles
        self.capacity, self.d = capacity, d
        #: the overflow retry's fold horizon and bound, as they were
        #: when the rows left: every op at or below them was staged by
        #: then, so it is in the ring or aboard.  Rows staged during
        #: the flight may lie below what the plane has learned since,
        #: and a fold there would cover them before they land
        self.stable, self.ring_bound = stable, ring_bound
        self.donating = True
        self.outs: list = []  # (device overflow mask, rows in chunk)
        self.dispatched = False
        self.overflow: Optional[np.ndarray] = None
        self.error: Optional[BaseException] = None
        self.settled = False
        self.t0 = time.perf_counter()


class _PlaneBase:
    """Shared machinery: key directory, pending rows, flush/gc plumbing."""

    type_name: str = ""

    def __init__(self, domain: ClockDomain, key_capacity: int,
                 n_lanes: int, flush_ops: int, gc_ops: int,
                 max_dcs: int,
                 ingest_settings: Optional[ingest.IngestSettings] = None):
        self.domain = domain
        self.n_lanes = n_lanes
        self.flush_ops = flush_ops
        self.gc_ops = gc_ops
        self.max_dcs = max_dcs
        #: coalesced-ingest knobs (mat/ingest.py): packed single-H2D
        #: flushes, the staging window, and the row budget.  Built by
        #: the one factory (ingest_from_config) at the DevicePlane /
        #: sharded-store assembly so every plane honors the same knobs.
        self._ingest = ingest_settings or ingest.ingest_from_config(None)
        #: monotonic µs stamp of the oldest staged row (drives the
        #: coalescing window); meaningless while ``rows`` is empty
        self._stage_t0_us = 0
        self.key_index: Dict[Any, int] = {}
        self.rev_keys: List[Any] = []
        #: staged decoded rows (lists of python ints / pair-lists)
        self.rows: List[tuple] = []
        self.pending_keys: set = set()
        self._ops_since_gc = 0
        self._base_vc = VC()
        self._has_base = False
        #: newest stable snapshot seen (GC horizon for overflow retries)
        self._last_stable: Optional[VC] = None
        #: cached device-resident "read latest" snapshot (one device_put
        #: per domain width instead of one per read)
        self._inf_rv = None
        #: set by the owning PartitionManager: evict a key's history to
        #: the host store (log replay; ``state`` carries the pre-purge
        #: device fold when there is no log to replay — see
        #: ``evict_export``)
        self.on_evict: Callable[..., None] = \
            lambda k, t, state=None: None
        #: set (via DevicePlane.set_evict_handler) when the owning
        #: partition has NO durable log: an eviction must materialize
        #: the key's host state from the device fold BEFORE dropping
        #: the lanes — replaying the empty log silently zeroed the key
        #: (the PR-7-flagged bug, reproduced on clean HEAD)
        self.evict_export = False
        #: same condition, shared with map sub-planes (which export at
        #: the MAP level): drives the flush overflow path's emergency
        #: fold — with no log, dropping an overflowed row is DATA LOSS,
        #: so the ring folds fully into the base to make room first
        self.no_log_replay = False
        #: host-side join of every staged op's commit VC — the honest
        #: base bound after an emergency full fold (ring ops are all
        #: published, so their commit VCs are below this join).  Only
        #: maintained when ``no_log_replay`` (DevicePlane.stage).
        self._ring_vc_bound = VC()
        #: re-entrancy guard: the export fold must not recurse through
        #: a flush back into this key's own eviction
        self._exporting: set = set()
        self.capacity = key_capacity
        self.st = self._init_state(key_capacity)
        #: background compile kicked on the FIRST staged op for this
        #: plane (DevicePlane.stage): warming every type at node build
        #: would compile 11 types' programs nobody may ever use —
        #: costly, and on small hosts the compile threads compete with
        #: serving
        self._warm_kicked = False
        #: mesh this plane's state is GSPMD-sharded over (set by
        #: DevicePlane.place_sharded; None = single-chip).  While set,
        #: every state-array dispatch is a MULTI-CHIP program and must
        #: serialize under runtime.COLLECTIVE_LOCK (_collective_cm)
        self._mesh = None
        #: per-shard residency router (mat/sharded.ShardRouter), wired
        #: alongside the mesh
        self._router = None
        #: chip this plane's state is committed to under ring placement
        #: (set by DevicePlane.place_on; None = the default device)
        self._device = None
        #: the routine flush out of the partition lock, if one is out
        #: (begin_flight .. settle_flight); at most one a plane
        self._flight: Optional[_Flight] = None
        #: held across a flight's dispatch, by the flusher or by the
        #: thread that lands the flight first (_join_flight)
        self._flight_lock = threading.Lock()
        #: a map's sub-plane (MapPlane._sub): read through the map's
        #: pending set, so its flushes stay in one hold (split_flush)
        self._in_map = False
        #: called under the partition lock when a flight settles: the
        #: owning PartitionManager's notify_all, which wakes the
        #: threads waiting for the flight (DevicePlane.set_settle_notify)
        self.on_settled: Callable[[], None] = lambda: None

    # -- subclass hooks -----------------------------------------------------

    def _init_state(self, key_capacity: int):
        raise NotImplementedError

    def _grow_dcs(self, new_d: int) -> None:
        raise NotImplementedError

    def _grow_keys(self, new_k: int) -> None:
        raise NotImplementedError

    #: row-field tags after the leading key index ("s" scalar / "vv"
    #: pair list) — must match the argument order of ``_append_fn``
    _row_cols: tuple = ()
    #: the store's ``*_append`` for this plane's shard state
    _append_fn = None

    def kick_warm(self) -> None:
        """Idempotent first-use trigger for warm_appends."""
        if not self._warm_kicked:
            self._warm_kicked = True
            self.warm_appends()

    def warm_appends(self, buckets: tuple = (64, 256)) -> None:
        """Compile this plane's append programs for every dispatch
        bucket BEFORE the serving path needs them, in a background
        thread (XLA compilation is C++ work that releases the GIL, so
        commits keep flowing).  Without this, the first flush at an
        unseen bucket shape pays a ~300 ms in-line compile UNDER the
        partition lock — measured as the dominant config6 p99 term and
        the cluster data node's commit convoy.  The warm rows are all
        padding (key index = capacity, _pack_rows' sentinel), so
        executing the program is a no-op on the discarded result."""
        if type(self)._append_fn is None or self._mesh is not None:
            # sharded planes never warm in the background: the copies'
            # dispatches are multi-chip programs, and a warm thread
            # cannot take COLLECTIVE_LOCK without convoying the
            # serving path behind a ~300ms compile
            return
        packed_mode = (self._ingest.enabled
                       and self._packed_perm() is not None)
        shapes = tuple(
            (tuple(x.shape), str(getattr(x, "dtype", "")))
            for x in jax.tree_util.tree_leaves(self.st))
        base_key = (id(ingest.packed_append) if packed_mode
                    else id(type(self)._append_fn), shapes)
        todo = []
        with _WARM_LOCK:
            for b in buckets:
                k = base_key + (b,)
                if k not in _WARMED:
                    _WARMED.add(k)
                    todo.append(b)
        if not todo:
            return
        d, cols, cap = self.domain.d, self._row_cols, self.capacity
        fn = type(self)._append_fn
        # the append DONATES its state buffers — warm on a copy, never
        # the live state.  The copy is taken HERE, synchronously: this
        # runs from __init__ (or a grow site under the partition lock),
        # before concurrent appends could donate the buffers out from
        # under a background tree_map.
        st_copy = jax.tree_util.tree_map(jnp.copy, self.st)

        def run():
            st = st_copy
            for b in todo:
                try:
                    if packed_mode:
                        # the serving path is the packed single-upload
                        # flush: warm ITS program at the same buckets
                        pk = np.zeros(
                            (b, 2 + ingest.packed_width(cols, d)),
                            dtype=np.int64)
                        pk[:, 0] = cap  # all padding: a no-op program
                        st, _over = ingest.packed_append(st, pk)
                        continue
                    ki = np.full(b, cap, dtype=np.int32)
                    lo = np.zeros(b, dtype=np.int32)
                    arrays = [np.zeros((b, d) if tag == "vv" else b,
                                       dtype=np.int64) for tag in cols]
                    st, _over = fn(st, ki, lo, *arrays)
                except Exception:  # noqa: BLE001 — warm is best-effort
                    # the serving path will meet the same program: a
                    # compiler refusal must be seen here first
                    log.warning("append warm failed (%s, bucket %d)",
                                self.type_name, b, exc_info=True)
                    return

        _start_warm(run, f"warm:{self.type_name}")

    def warm_reads(self) -> None:
        """Background-compile this plane's READ fold at the CURRENT
        state shapes.  The first read after a capacity growth
        recompiles the fold on whatever client thread issued it —
        measured 0.35-1 s inline (the dominant config6 p99 spike
        together with the growth itself); warming runs it on a copy in
        a compile thread instead.  One program: the first dispatch
        bucket, which is also what one key pads to (:meth:`read`)."""
        if self._mesh is not None:
            return  # see warm_appends: no background mesh dispatches
        shapes = tuple(
            (tuple(x.shape), str(getattr(x, "dtype", "")))
            for x in jax.tree_util.tree_leaves(self.st))
        b = _bucket(1)
        k = ("read", id(type(self)), shapes, b)
        with _WARM_LOCK:
            if k in _WARMED:
                return
            _WARMED.add(k)
        try:
            rv = self._read_vc_dense(None)
        except ReadBelowBase:  # pragma: no cover — latest never raises
            return
        # reads are pure but appends DONATE the state buffers — warm on
        # a copy taken here, under the caller's partition lock
        st_copy = jax.tree_util.tree_map(jnp.copy, self.st)
        try:
            (fn, args), _post = self._many_split(
                st_copy, [], np.zeros(0, dtype=np.int32),
                np.zeros(b, dtype=np.int32), rv)
        except NotImplementedError:
            return  # per-document planes (RGA) have no batch fold

        def run():
            try:
                jax.block_until_ready(fn(*args))
            except Exception:  # noqa: BLE001 — warm is best-effort
                log.warning("read warm failed (%s)", self.type_name,
                            exc_info=True)

        _start_warm(run, f"warm-read:{self.type_name}")

    def _collective_cm(self):
        """COLLECTIVE_LOCK while mesh-sharded (every dispatch on the
        state is a multi-chip program — runtime.py's invariant), a
        no-op context on the single-chip path."""
        if self._mesh is not None:
            return COLLECTIVE_LOCK
        return contextlib.nullcontext()

    def _reshard(self) -> None:
        """Re-place the state per the rule table (mat/sharded.py).
        GSPMD does not promise jit outputs keep their inputs'
        shardings, and a grow rebuilds arrays on the default device —
        re-placing after every flush/GC/grow keeps drift from
        accumulating (device_put to an identical sharding is free)."""
        if self._mesh is not None:
            from antidote_tpu.mat import sharded as _sharded

            self.st = _sharded.place_state(self._mesh, self.st)

    def _post_grow(self) -> None:
        """After any capacity growth: put the regrown arrays back where
        the plane lives — a grow is a host repack that rebuilds them on
        the DEFAULT device, uncommitted — then compile the append AND
        read programs for the new shapes off the serving threads.  A
        mesh-sharded plane re-shards in place (and never warms in the
        background); a ring-placed one re-commits to its chip: left on
        the default device, every partition's fold would name chip 0
        as its device until the next append moved the state back, and
        a cross-partition read in that window fuses planes of several
        chips into one program, which JAX refuses."""
        if self._mesh is not None:
            self._reshard()
            return
        if self._device is not None:
            self.st = jax.device_put(self.st, self._device)
        self.warm_appends()
        self.warm_reads()

    def _packed_perm(self):
        """Ops-layout permutation for this plane's packed flushes, or
        None when the store has no packed form."""
        return ingest.perm_for(type(self)._append_fn)

    def _append_rows(self, rows: List[tuple]) -> np.ndarray:
        """Device-append decoded rows; returns bool[n] overflow.

        Coalesced path (mat/ingest.py, default): ONE packed host
        tensor, ONE upload (the jitted call's own, of the NumPy
        argument), one donated-scatter dispatch.  Legacy path
        (``mat_ingest=False``): the historical per-column packing —
        ~10 separate uploads per flush — kept as the benches'
        comparison baseline."""
        n = len(rows)
        if n == 0:
            return np.zeros(0, dtype=bool)
        overflow = self._dispatch_rows(rows, self.capacity, self.domain.d)
        return self._fetch_overflow([(overflow, n)])

    def _dispatch_rows(self, rows: List[tuple], capacity: int, d: int):
        """The dispatch half of :meth:`_append_rows`: pack ``rows``
        (decoded at ``capacity`` keys and ``d`` clock columns) and
        enqueue the append on ``self.st``, which it donates; returns
        the overflow mask as a device array."""
        n = len(rows)
        perm = self._packed_perm()
        if self._ingest.enabled and perm is not None:
            packed = ingest.pack_rows(rows, capacity, d, self._row_cols,
                                      perm)
            with self._collective_cm(), \
                    tracer.span("device_dispatch", "device",
                                plane=self.type_name, rows=n):
                self.st, overflow = ingest.packed_append(self.st, packed)
            ingest.note_dispatch(
                n, packed.nbytes,
                replicas=(self._mesh.shape["part"]
                          if self._mesh is not None else 1))
        else:
            ki, lo, arrays = _pack_rows(rows, capacity, d, self._row_cols)
            with self._collective_cm(), \
                    tracer.span("device_dispatch", "device",
                                plane=self.type_name, rows=n):
                self.st, overflow = type(self)._append_fn(
                    self.st, ki, lo, *arrays)
        return overflow

    def _fetch_overflow(self, outs: list) -> np.ndarray:
        """The fetch half: the masks of ``outs`` = [(device mask, rows
        in its chunk)] on the host, one bool a row."""
        with tracer.wait_span("device_fetch", "device",
                              plane=self.type_name):
            return np.concatenate(
                [np.asarray(o)[:n] for o, n in outs]
                or [np.zeros(0, dtype=bool)])

    def _purge_idx(self, idx: int) -> None:
        raise NotImplementedError

    def _device_gc(self, gst_dense: np.ndarray) -> None:
        raise NotImplementedError

    def _run_device_gc(self, gst_dense: np.ndarray) -> None:
        """The one `_device_gc` launch point: serialized under the
        collective lock while mesh-sharded (the fold is a multi-chip
        program)."""
        with self._collective_cm():
            self._device_gc(gst_dense)

    # -- directories --------------------------------------------------------

    def _dc_col(self, actor) -> Optional[int]:
        """Dense column for a DC id / dot actor; None = over capacity."""
        if not self.domain.contains(actor):
            if len(self.domain) >= self.max_dcs:
                return None
            if len(self.domain) >= self.domain.d:
                self.flush("grow")  # staged rows decoded at the old width
                new_d = min(self.domain.d * 2, self.max_dcs)
                self.domain = self.domain.grow(new_d)
                self._grow_dcs(new_d)
                self._post_grow()
        return self.domain.index_of(actor)

    def _key_idx(self, key) -> int:
        idx = self.key_index.get(key)
        if idx is None:
            if len(self.rev_keys) >= self.capacity:
                self.flush("grow")
                self.capacity *= 2
                self._grow_keys(self.capacity)
                self._post_grow()
            idx = len(self.rev_keys)
            self.key_index[key] = idx
            self.rev_keys.append(key)
        return idx

    def _ss_pairs(self, vc: VC) -> Optional[List[tuple]]:
        out = []
        for dc, t in vc.items():
            if not t:
                continue
            col = self._dc_col(dc)
            if col is None:
                return None
            out.append((col, int(t)))
        return out

    def _dense_vc(self, pairs: List[tuple]) -> np.ndarray:
        row = np.zeros(self.domain.d, dtype=np.int64)
        for col, t in pairs:
            row[col] = max(row[col], t)
        return row

    def _decode_obs(self, observed) -> Optional[List[tuple]]:
        """Dense (col, seq) pairs for an observed-dot list; None on a
        DC-column capacity miss (caller evicts to the host path)."""
        out = []
        for a, s in observed:
            col = self._dc_col(a)
            if col is None:
                return None
            out.append((col, int(s)))
        return out

    def _note_staged_vc(self, payload: Payload) -> None:
        """Track the join of staged commit VCs (unlogged mode only) —
        the honest base bound the emergency fold raises to."""
        if self.no_log_replay:
            self._ring_vc_bound = self._ring_vc_bound.join(
                payload.commit_vc())

    def _commit_rows(self, key, idx: int, rows: List[tuple]) -> None:
        """Stage decoded rows — unless a growth-triggered flush evicted
        the key mid-stage (the migration replayed the log, which already
        holds this op; staging would write into purged lanes)."""
        if self.key_index.get(key) != idx:
            return
        if not self.rows:
            self._stage_t0_us = time.monotonic_ns() // 1000
        self.rows.extend(rows)
        self.pending_keys.add(key)

    # -- lock-free read split ------------------------------------------------

    def read_many_begin(self, keys: list, read_vc: Optional[VC]):
        """The capture of every read, one key or many.  MUST run under
        the partition lock: flush the keys' staged rows, resolve
        directories, and capture the (immutable) device state — one
        captured state + one device fold for every device-owned key in
        ``keys``.  Returns a closure yielding {key: value} (non-owned
        keys absent — callers serve them from the host path) that may
        run OUTSIDE the lock: the shard state is a functional pytree,
        so a concurrent flush/GC only swaps ``self.st`` with a new
        value and never mutates what the closure captured.  The host
        arrays it captured beside the state (the padded index vector,
        an explicit snapshot's dense row) stay NumPy until the jitted
        call takes them as its arguments — uploading them here would
        be Python-path work under the lock — and are a snapshot all
        the same: each is built fresh per capture and nothing writes
        it afterwards.  This is the read-concurrency analogue of the
        reference's shared-ETS readers next to the vnode process
        (reference src/clocksi_readitem_server.erl:95-110).  A flight
        out that holds the state or carries one of ``keys`` lands
        first; ``PartitionManager.read_many_begin`` refuses before it
        gets here, so a read's capture never waits."""
        if self.flight_blocks(keys):
            self._join_flight()
        if self.pending_keys and not self.pending_keys.isdisjoint(keys):
            self.flush("read")
        owned = [k for k in keys if k in self.key_index]
        if not owned:
            return dict
        # argument preparation: the indices and the snapshot as host
        # arrays; the dispatch's jitted call uploads them
        with tracer.span("device_prepare", "device",
                         plane=self.type_name, keys=len(owned)):
            rv = self._read_vc_dense(read_vc)
            idxs = np.asarray([self.key_index[k] for k in owned],
                              dtype=np.int32)
            pad = np.zeros(_bucket(len(idxs)), dtype=np.int32)
            pad[:len(idxs)] = idxs
            return self._many_reader(self.st, owned, idxs, pad, rv)

    def _many_split(self, st, owned: list, idxs: np.ndarray,
                    pad: np.ndarray, rv):
        """Subclass hook: ``((fn, args), post)`` — the batched read
        split into its device half (a jitted store call; ``fn(*args)``
        yields the fold's array pytree) and its host half (``post``
        maps the np-converted arrays to {key: state}).  The split is
        what lets the FUSED cross-partition path (fused_read, below)
        run many planes' folds from one chip as a single XLA program
        — one dispatch per chip instead of one per partition."""
        raise NotImplementedError

    def _many_reader(self, st, owned: list, idxs: np.ndarray,
                     pad: np.ndarray, rv):
        """Closure materializing the owned keys in one batched fold of
        the captured state (``pad`` = idxs padded to the dispatch
        bucket).  Carries ``.split``/``.device`` so a cross-partition
        caller can fuse this fold with other planes' (see fused_read),
        and ``.halves`` = (fetch, post) for a caller that gives its
        reader count back as soon as the captured buffers are done
        with: ``fetch()`` is the device half, ending with the values
        on the host; ``post`` decodes them and touches no device state
        (PartitionManager._ckpt_fold).  Planes with no batched-fold
        form (RGA's per-document trees) override this without
        either."""
        spec, post = self._many_split(st, owned, idxs, pad, rv)
        fn, args = spec

        def fetch():
            count_read_dispatch()
            with self._collective_cm():
                with tracer.span("device_dispatch", "device",
                                 plane=self.type_name):
                    out = fn(*args)
                with tracer.wait_span("device_fetch", "device",
                                      plane=self.type_name):
                    return jax.tree_util.tree_map(np.asarray, out)

        def run():
            return post(fetch())

        run.split = (spec, post)
        run.halves = (fetch, post)
        if self._mesh is not None:
            # the mesh IS the fusing discriminator: every sharded
            # plane's fold is the same multi-chip program family, so
            # cross-partition callers group them all into ONE
            # fused_read (leaf.devices() would be nondeterministic
            # for a sharded array — any of N chips — and break the
            # grouping)
            run.device = self._mesh
        else:
            leaf = jax.tree_util.tree_leaves(st)[0]
            run.device = next(iter(leaf.devices())) \
                if hasattr(leaf, "devices") else None
        return run

    def read_many(self, keys: list, read_vc: Optional[VC]) -> dict:
        """{key: state} for device-owned keys; callers take the host
        path for the rest."""
        return self.read_many_begin(keys, read_vc)()

    def read(self, key, read_vc: Optional[VC]):
        """The key's host-CRDT state at ``read_vc``: the batched read
        with one key in it (state shape as each subclass's
        ``_many_split`` decodes it).  ReadBelowBase where the key is
        not this plane's once its staged rows are flushed — evicted,
        or never here: the caller takes the host/log path."""
        got = self.read_many([key], read_vc)
        if key not in got:
            raise ReadBelowBase()
        return got[key]

    def seed_effects(self, state) -> Optional[list]:
        """Effects that rebuild ``state`` exactly from bottom when
        staged through this plane's own decoder — the checkpoint-seed
        device re-init (ISSUE 13): a restarted node re-ingests each
        folded seed as ordinary rows (the packed ingest upload) and
        folds them into the device base at the seed frontier.  None =
        this plane cannot represent a bare state as effects (RGA's
        per-document trees, the STATE_LOSSY dot collapses) — the key
        stays on the host path, exactly the pre-seed behavior.  The
        round trip is the inverse of the read/the evict export:
        seed_effects(read()) staged onto an empty plane reads back
        identical (pinned per type by tests/unit/test_ckpt_segments
        .py)."""
        return None

    # -- lifecycle ----------------------------------------------------------

    def owns(self, key) -> bool:
        return key in self.key_index

    def _export_evict_state(self, key):
        """The key's latest device-fold state, captured BEFORE the
        purge, when there is no log to replay (``evict_export``);
        None otherwise.  Best-effort: a failed export falls back to
        the (empty) log replay rather than wedging the eviction."""
        if not self.evict_export or key in self._exporting:
            return None
        self._exporting.add(key)
        try:
            return self.read(key, None)
        except Exception:  # noqa: BLE001 — export must not break evict
            log.exception(
                "evict-state export failed for %r (%s); the key's "
                "unlogged history cannot migrate to the host store",
                key, self.type_name)
            return None
        finally:
            self._exporting.discard(key)

    def evict(self, key) -> None:
        """Purge the key's device rows and hand its history to the host
        path (on_evict replays the log into the host store; with no log
        to replay, the pre-purge device fold travels along — the state
        the host store is seeded from)."""
        self._join_flight()  # the purge donates the state
        idx = self.key_index.get(key)
        if idx is None:
            return
        state = self._export_evict_state(key)
        self.key_index.pop(key, None)
        self.rows = [r for r in self.rows if r[0] != idx]
        self.pending_keys.discard(key)
        self.rev_keys[idx] = _Evicted
        with self._collective_cm():
            self._purge_idx(idx)
        if self._router is not None:
            # the owning shard's lanes just overflowed (or the key was
            # displaced): charge that shard's economy so it stops
            # admitting new device residents until the next fold
            self._router.note_evict(idx, self.capacity)
        log.debug("device plane: evicted %r (%s)", key, self.type_name)
        recorder.record("device", "evict", plane=self.type_name,
                        key=key)
        self.on_evict(key, self.type_name, state)

    #: set by DevicePlane.stage when async flushing is wired: called
    #: with this plane to run flush/gc on the flusher thread
    _schedule = None

    def _window_due(self, n_rows: int) -> bool:
        """True when staged rows outlived the coalescing window
        (mat_coalesce_us): the next stage tick flushes the whole burst
        as one dispatch even below the flush_ops threshold — bounded
        device-state staleness, the gate-ring window's plane analogue."""
        return (n_rows > 0 and self._ingest.coalesce_us > 0
                and (time.monotonic_ns() // 1000 - self._stage_t0_us)
                >= self._ingest.coalesce_us)

    def maybe_flush_gc(self, stable_vc: Optional[VC]) -> None:
        if stable_vc is not None:
            self._last_stable = (stable_vc if self._last_stable is None
                                 else self._last_stable.join(stable_vc))
        n_rows = len(self.rows)
        window_due = self._window_due(n_rows)
        due_flush = n_rows >= self.flush_ops or window_due
        due_gc = (stable_vc is not None
                  and self._ops_since_gc >= self.gc_ops)
        if not (due_flush or due_gc):
            return
        if self._schedule is not None \
                and n_rows < min(4 * self.flush_ops,
                                 self._ingest.row_budget):
            # group commit: the committing transaction only stages; the
            # device work runs on the flusher thread.  Past 4x the
            # threshold (or the ingest row budget, whichever is
            # tighter) the committer flushes INLINE — backpressure so
            # a lagging flusher cannot let staged rows grow unboundedly
            self._schedule(self)
            return
        if due_flush:
            if n_rows >= self._ingest.row_budget:
                kind = "budget"
            elif n_rows >= self.flush_ops:
                kind = "rows"
            else:
                kind = "window"
            self.flush(kind)
        if due_gc:
            self.gc(self._last_stable or stable_vc)

    def flush_due(self) -> Optional[str]:
        """The kind of flush the flusher owes this plane now — ``rows``
        past the threshold, ``window`` past the coalescing window — or
        None."""
        n_rows = len(self.rows)
        if n_rows >= self.flush_ops:
            return "rows"
        if self._window_due(n_rows):
            return "window"
        return None

    def flush_gc_now(self) -> None:
        """Flusher-thread entry: run any due flush/GC in one hold
        (caller holds the partition lock and has quiesced device
        readers)."""
        kind = self.flush_due()
        if kind is not None:
            self.flush(kind)
        self.gc_grow_now()

    def gc_grow_due(self) -> bool:
        """Whether :meth:`gc_grow_now` has anything to do."""
        return self._gc_due() or self._grow_due()

    def gc_grow_now(self) -> None:
        """The rest of :meth:`flush_gc_now`: a due GC fold and the
        speculative grow."""
        if self._gc_due():
            self.gc(self._last_stable)
        self._maybe_speculative_grow()

    def _gc_due(self) -> bool:
        return (self._last_stable is not None
                and self._ops_since_gc >= self.gc_ops)

    def _grow_due(self) -> bool:
        return len(self.rev_keys) * 8 >= self.capacity * 7

    # -- the routine flush out of the partition lock ------------------------

    @property
    def split_flush(self) -> bool:
        """Whether the flusher's routine flush may leave the partition
        lock across its dispatch and its fetch: a single-chip plane
        whose state is one donated pytree appended by
        :meth:`_append_rows`.  Mesh-sharded planes (one multi-chip
        program under the collective lock), a map's sub-planes (whose
        readers check the map's pending set, not theirs) and RGA's
        per-document states flush in one hold."""
        return (self._mesh is None and not self._in_map
                and type(self)._append_rows is _PlaneBase._append_rows)

    def flight_blocks(self, keys) -> bool:
        """True while the flight out holds the state (``donating``) or
        carries one of ``keys``: a capture must not be made (under the
        partition lock)."""
        f = self._flight
        return f is not None and (f.donating or not f.keys.isdisjoint(keys))

    def begin_flight(self, kind: str) -> _Flight:
        """Under the partition lock, device readers quiesced: take the
        staged rows and their keys out as a flight whose state is
        marked donated.  Keys staged from here on are pending again
        through ``pending_keys``."""
        self._join_flight()
        ingest.note_flush(kind)
        f = _Flight(self.rows, self.pending_keys, self.capacity,
                    self.domain.d, self._last_stable, self._ring_vc_bound)
        self.rows, self.pending_keys = [], set()
        self._flight = f
        return f

    def dispatch_flight(self, f: _Flight) -> None:
        """The flight's dispatch, outside every partition lock: its
        rows enqueued (chunked as :meth:`flush` chunks), which ends the
        donation — captures of keys it does not carry may go on.  A
        thread that had to touch the plane may have done it first
        (_join_flight)."""
        with self._flight_lock:
            if not f.dispatched:
                self._dispatch_flight(f)

    def fetch_flight(self, f: _Flight) -> None:
        """The flight's fetch of the overflow mask, outside every
        partition lock (nothing to do once another thread settled
        it)."""
        if not f.settled and f.error is None:
            f.overflow = self._fetch_overflow(f.outs)

    def _dispatch_flight(self, f: _Flight) -> None:
        """Enqueue the flight's appends; under ``_flight_lock``."""
        try:
            step = max(self.flush_ops, _MIN_BUCKET)
            for i in range(0, len(f.rows), step):
                chunk = f.rows[i:i + step]
                f.outs.append((self._dispatch_rows(chunk, f.capacity, f.d),
                               len(chunk)))
        except BaseException as e:
            f.error = e
            raise
        finally:
            f.donating, f.dispatched = False, True

    def _join_flight(self) -> None:
        """Land the flight out before this plane's state is touched:
        every path that mutates, copies or captures what the flight
        donates or carries comes here first, under the partition lock,
        and the hold stays one hold — a publish that grows, evicts or
        flushes this plane must not give the lock away (a commit record
        never sits in the log across a release before its effects are
        published).  So it takes ``_flight_lock`` after the partition
        lock: held by the flusher only across its dispatch, inside
        which it takes no other lock but leaves (counters, the tracer),
        it comes free.  A flight not yet dispatched is dispatched here;
        then the mask is fetched, if the flusher has not, and the
        flight settles."""
        f = self._flight
        if f is None:
            return
        stats.registry.device_flush_inflight_waits.inc()
        try:
            if not self._flight_lock.acquire(False):
                with tracer.wait_span("device_quiesce_wait", "device",
                                      plane=self.type_name, flush=1):
                    self._flight_lock.acquire()
            try:
                if not f.dispatched:
                    self._dispatch_flight(f)
            finally:
                self._flight_lock.release()
        finally:
            if f.overflow is None and f.error is None:
                f.overflow = self._fetch_overflow(f.outs)
            self.settle_flight(f, f.overflow)

    def settle_flight(self, f: _Flight,
                      overflow: Optional[np.ndarray]) -> None:
        """Under the partition lock, once: take the flight off the
        plane and account for it.  With no row overflowed that is all
        (keys staged again meanwhile stay pending through
        ``pending_keys``); otherwise today's retry path runs here, in
        this hold — the caller has quiesced device readers and marked
        the state donated.  A flight whose dispatch or fetch failed
        lost its rows, as a failed flush in one hold does."""
        if f.settled:
            return
        f.settled = True
        self._flight = None
        try:
            if f.error is not None or overflow is None:
                return
            self._ops_since_gc += len(f.rows)
            if overflow.any():
                stats.registry.device_flush_split.inc(outcome="overflow")
                with tracer.span(f"device_flush:{self.type_name}",
                                 "device", rows=len(f.rows)):
                    self._settle_overflow(f.rows, overflow, f.stable,
                                          f.ring_bound)
            else:
                stats.registry.device_flush_split.inc(outcome="clean")
            self._reshard()
            stats.registry.device_flush_latency.observe(
                time.perf_counter() - f.t0)
            recorder.record("device", "flush", plane=self.type_name,
                            rows=len(f.rows),
                            overflow=int(overflow.sum()))
        finally:
            self.on_settled()

    def _maybe_speculative_grow(self) -> None:
        """Double the key directory BEFORE stage() must do it inline:
        a grow is a host repack + re-upload plus fresh XLA programs at
        the new shapes — on the commit path that was the dominant
        config6 p99 term (0.7-2.5 s in-run recompile spikes after a
        doubling).  Here it runs on the background flusher, under the
        partition lock with readers quiesced, and the new programs
        warm before the serving threads first use them."""
        if self._grow_due():
            self.flush("grow")
            self.capacity *= 2
            self._grow_keys(self.capacity)
            self._post_grow()

    def flush(self, kind: str = "explicit") -> None:
        """Drain staged rows into the device ring, padded to a bucket.
        Rows whose key ring is full force a GC at the newest stable
        snapshot and one retry; still-overflowing keys evict to the
        host path.  ``kind`` labels the flush trigger for the INGEST_*
        counters (mat/ingest.py INGEST_FLUSH_KINDS).  One hold: the
        flusher's routine flush of a :attr:`split_flush` plane takes
        the other road (begin_flight .. settle_flight)."""
        self._join_flight()
        if not self.rows:
            return
        ingest.note_flush(kind)
        stats.registry.device_flush_split.inc(outcome="whole")
        rows, self.rows = self.rows, []
        self.pending_keys.clear()
        # chunk at the configured batch size: a backlog above flush_ops
        # would otherwise pad to a LARGER bucket and compile a fresh XLA
        # program mid-run (one 700ms stall per new shape on CPU); the
        # chunk size is the intended steady-state batch anyway
        step = max(self.flush_ops, _MIN_BUCKET)
        overflow = np.zeros(len(rows), dtype=bool)
        t0 = time.perf_counter()
        # the span and histogram cover the overflow-retry path too —
        # the forced GC + second append (possibly a fresh XLA compile)
        # dominate exactly the flushes the stage-latency panel hunts
        with tracer.span(f"device_flush:{self.type_name}", "device",
                         rows=len(rows)):
            for i in range(0, len(rows), step):
                overflow[i:i + step] = self._append_rows(
                    rows[i:i + step])
            self._ops_since_gc += len(rows)
            if overflow.any():
                self._settle_overflow(rows, overflow, self._last_stable,
                                      self._ring_vc_bound)
        self._reshard()
        stats.registry.device_flush_latency.observe(
            time.perf_counter() - t0)
        recorder.record("device", "flush", plane=self.type_name,
                        rows=len(rows),
                        overflow=int(overflow.sum()))

    def _settle_overflow(self, rows: List[tuple], overflow: np.ndarray,
                         stable: Optional[VC], ring_bound: VC) -> None:
        """The retry path of a flush some of whose rows overflowed
        their key's ring: a GC at the stable snapshot ``stable`` and
        one retry; still-overflowing keys evict to the host path.
        Under the partition lock with device readers quiesced,
        whichever road the flush took; ``stable`` and ``ring_bound``
        are the plane's as the rows were taken out."""
        retry = [r for r, o in zip(rows, overflow) if o]
        gst = None
        if stable is not None:
            pairs = self._ss_pairs(stable)
            if pairs is not None:
                gst = self._dense_vc(pairs)
                self._run_device_gc(gst)
                self._base_vc = self._base_vc.join(stable)
                self._has_base = True
                self._ops_since_gc = 0
        overflow2 = self._append_rows(retry)
        if gst is not None:
            # invariant: every ring op with commit VC <= base_vc must be
            # folded INTO the base — the retried rows landed after the
            # fold above, so fold once more at the same horizon (rows
            # above it are untouched)
            self._run_device_gc(gst)
        if overflow2.any() and self.no_log_replay:
            # EMERGENCY fold (unlogged mode): dropping an overflowed row
            # here is permanent data loss — no log exists to replay it
            # from — so fold the WHOLE ring into the base to free lanes
            # and retry once more.  Sound: every ring op is published,
            # so the host-side join of staged commit VCs bounds them;
            # reads below the raised base take the log-replay path,
            # which unlogged mode already degrades.
            inf = np.full(self.domain.d, _VC_INF, dtype=np.int64)
            self._run_device_gc(inf)
            self._base_vc = self._base_vc.join(ring_bound)
            self._has_base = True
            self._ops_since_gc = 0
            retry2 = [r for r, o in zip(retry, overflow2) if o]
            overflow3 = self._append_rows(retry2)
            if overflow3.any():
                # structural caps (slots / DC columns): the rows are
                # unrepresentable and, unlogged, unrecoverable — keep
                # the loss loud
                recorder.record("device", "evict_lost_rows",
                                plane=self.type_name,
                                rows=int(overflow3.sum()))
            retry, overflow2 = retry2, overflow3
        bad_keys = {self.rev_keys[r[0]]
                    for r, o in zip(retry, overflow2) if o}
        for key in bad_keys:
            if key is not _Evicted:
                self.evict(key)

    def gc(self, stable_vc: VC) -> None:
        """Fold ops at/below the gossiped stable snapshot into the base
        (store.orset_gc / counter_gc contract: the GST is stable, folding
        is permanent)."""
        # let the flush's overflow-retry fold at this horizon too
        self._last_stable = (stable_vc if self._last_stable is None
                             else self._last_stable.join(stable_vc))
        self.flush("gc")
        pairs = self._ss_pairs(stable_vc)
        if pairs is None:
            return
        with tracer.span(f"device_gc:{self.type_name}", "device"):
            self._run_device_gc(self._dense_vc(pairs))
        self._reshard()
        if self._router is not None:
            # a fold freed ring lanes on every shard — reset the
            # overflow economy so shards re-admit device residents
            self._router.note_fold()
        recorder.record("device", "gc", plane=self.type_name,
                        horizon=dict(stable_vc))
        self._base_vc = self._base_vc.join(stable_vc)
        self._has_base = True
        self._ops_since_gc = 0

    def _read_vc_dense(self, read_vc: Optional[VC]):
        """Dense read snapshot (np for explicit VCs, the cached device
        array for read-latest — treat as immutable); raises
        ReadBelowBase when the requested snapshot does not dominate the
        device base (caller replays log)."""
        if read_vc is None:
            if self._inf_rv is None or \
                    self._inf_rv.shape[0] != self.domain.d:
                self._inf_rv = jnp.full((self.domain.d,), _VC_INF,
                                        dtype=jnp.int64)
            return self._inf_rv
        if self._has_base and not self._base_vc.le(read_vc):
            raise ReadBelowBase()
        pairs = self._ss_pairs(read_vc)
        if pairs is None:
            raise ReadBelowBase()  # unknown-DC flood: serve from log
        return self._dense_vc(pairs)


class _Evicted:
    """Sentinel occupying the rev_keys slot of an evicted key."""


class OrsetPlane(_PlaneBase):
    """Device plane for set_aw.  Row tuple:
    (key_idx, slot, is_add, dot_col, dot_seq, obs_pairs, op_dc_col,
    op_ct, ss_pairs)."""

    type_name = "set_aw"
    # (slot, is_add, dot_dc, dot_seq, obs_vv, op_dc, op_ct, op_ss)
    _row_cols = ("s", "s", "s", "s", "vv", "s", "s", "vv")
    _append_fn = staticmethod(store.orset_append)

    def __init__(self, domain, key_capacity, n_lanes, n_slots, flush_ops,
                 gc_ops, max_dcs, max_slots, ingest_settings=None):
        self.n_slots = n_slots
        self.max_slots = max_slots
        #: per key-idx: element -> slot and slot -> element
        self.elem_index: List[Dict[Any, int]] = []
        self.rev_elems: List[List[Any]] = []
        super().__init__(domain, key_capacity, n_lanes, flush_ops,
                         gc_ops, max_dcs,
                         ingest_settings=ingest_settings)

    def _init_state(self, key_capacity):
        return store.orset_shard_init(
            key_capacity, self.n_lanes, self.n_slots, self.domain.d,
            dtype=jnp.int64)

    def _grow_dcs(self, new_d):
        self.st = store.orset_grow(self.st, n_dcs=new_d)

    def _grow_keys(self, new_k):
        self.st = store.orset_grow(self.st, n_keys=new_k)

    def _grow_slots(self, new_e):
        self.flush("grow")
        self.n_slots = new_e
        self.st = store.orset_grow(self.st, n_slots=new_e)

    def _key_idx(self, key):
        idx = super()._key_idx(key)
        while len(self.elem_index) <= idx:
            self.elem_index.append({})
            self.rev_elems.append([])
        return idx

    def _slot(self, idx: int, elem) -> Optional[int]:
        slots = self.elem_index[idx]
        s = slots.get(elem)
        if s is None:
            if len(slots) >= self.n_slots:
                if len(slots) >= self.max_slots:
                    return None
                self._grow_slots(min(self.n_slots * 2, self.max_slots))
                self._post_grow()
            s = len(slots)
            slots[elem] = s
            self.rev_elems[idx].append(elem)
        return s

    def stage(self, key, payload: Payload) -> None:
        """Decode one committed set_aw effect into device rows; evicts
        the key (host fallback) on any capacity miss."""
        idx = self._key_idx(key)
        kind, entries = payload.effect
        op_dc_col = self._dc_col(payload.commit_dc)
        ss_pairs = self._ss_pairs(payload.snapshot_vc)
        if op_dc_col is None or ss_pairs is None:
            self.evict(key)
            return
        rows = []
        for entry in entries:
            if kind == "add":
                elem, dot, observed = entry
                actor, seq = dot
                dot_col = self._dc_col(actor)
                is_add = 1
            else:  # "rmv"
                elem, observed = entry
                dot_col, seq, is_add = 0, 0, 0
            slot = self._slot(idx, elem)
            obs_pairs = self._decode_obs(observed)
            if slot is None or obs_pairs is None or (
                    is_add and dot_col is None):
                self.evict(key)
                return
            rows.append((idx, slot, is_add, dot_col or 0, int(seq),
                         obs_pairs, op_dc_col, int(payload.commit_time),
                         ss_pairs))
        self._commit_rows(key, idx, rows)

    def seed_effects(self, state):
        # state: {elem: frozenset((actor, seq))} — one add per live
        # dot, empty observed set (removes nothing): the union of dots
        # IS the state, exactly what the read reconstructs.  One ROW
        # per effect, so the seeder can chunk-fold dot-heavy keys
        # against the per-key lane budget.
        return [("add", [(elem, dot, ())])
                for elem, dots in state.items() for dot in dots]

    def _purge_idx(self, idx):
        self.st = store.orset_purge_keys(
            self.st, np.asarray([idx], dtype=np.int32))
        self.elem_index[idx] = {}
        self.rev_elems[idx] = []

    def _device_gc(self, gst_dense):
        self.st = store.orset_gc(self.st, gst_dense)

    def _many_split(self, st, owned, idxs, pad, rv):
        # captured under the lock; safe after release (see
        # read_many_begin): rev_elems[i] / dc_ids are append-only, st
        # is immutable
        elem_lists = [self.rev_elems[i] for i in idxs]
        domain = self.domain

        def post(dots):
            actors = domain.dc_ids
            out = {}
            for i, k in enumerate(owned):
                state = {}
                for slot, elem in enumerate(list(elem_lists[i])):
                    if slot >= dots.shape[1]:
                        break  # slot grown after the capture: no dots yet
                    live = frozenset(
                        (actors[j], int(s))
                        for j, s in enumerate(dots[i, slot][:len(actors)])
                        if s > 0)
                    if live:
                        state[elem] = live
                out[k] = state
            return out

        return ((store.orset_read_keys, (st, pad, rv)), post)


class CounterPlane(_PlaneBase):
    """Device plane for counter_pn.  Row tuple:
    (key_idx, delta, op_dc_col, op_ct, ss_pairs)."""

    type_name = "counter_pn"
    # (delta, op_dc, op_ct, op_ss)
    _row_cols = ("s", "s", "s", "vv")
    _append_fn = staticmethod(store.counter_append)

    def _init_state(self, key_capacity):
        return store.counter_shard_init(
            key_capacity, self.n_lanes, self.domain.d, dtype=jnp.int64)

    def _grow_dcs(self, new_d):
        self.st = store.counter_grow(self.st, n_dcs=new_d)

    def _grow_keys(self, new_k):
        self.st = store.counter_grow(self.st, n_keys=new_k)

    def stage(self, key, payload: Payload) -> None:
        idx = self._key_idx(key)
        op_dc_col = self._dc_col(payload.commit_dc)
        ss_pairs = self._ss_pairs(payload.snapshot_vc)
        if op_dc_col is None or ss_pairs is None:
            self.evict(key)
            return
        self._commit_rows(key, idx, [
            (idx, int(payload.effect), op_dc_col,
             int(payload.commit_time), ss_pairs)])

    def seed_effects(self, state):
        # state: int — one delta op rebuilds it
        return [int(state)] if state else []

    def _purge_idx(self, idx):
        self.st = store.counter_purge_keys(
            self.st, np.asarray([idx], dtype=np.int32))

    def _device_gc(self, gst_dense):
        self.st = store.counter_gc(self.st, gst_dense)

    def _many_split(self, st, owned, idxs, pad, rv):
        def post(vals):
            return {k: int(vals[i]) for i, k in enumerate(owned)}

        return ((store.counter_read_keys, (st, pad, rv)), post)


class MvregPlane(OrsetPlane):
    """Device plane for register_mv — the OR-Set ring with value slots
    (see store.py mvreg notes).  Row tuple identical to OrsetPlane's
    with elem := interned value; a reset row carries slot=n_slots (the
    drop slot) and seq=0, contributing only its observed VV."""

    type_name = "register_mv"

    def stage(self, key, payload: Payload) -> None:
        idx = self._key_idx(key)
        eff = payload.effect
        op_dc_col = self._dc_col(payload.commit_dc)
        ss_pairs = self._ss_pairs(payload.snapshot_vc)
        if op_dc_col is None or ss_pairs is None:
            self.evict(key)
            return
        if eff[0] == "asgn":
            _, v, dot, observed = eff
            try:
                slot = self._slot(idx, v)
            except TypeError:  # unhashable value — host path
                slot = None
            actor, seq = dot
            dot_col = self._dc_col(actor)
            ok = slot is not None and dot_col is not None
        else:  # "reset"
            _, observed = eff
            slot, dot_col, seq, ok = self.n_slots, 0, 0, True
        obs_pairs = self._decode_obs(observed) if ok else None
        if obs_pairs is None:
            self.evict(key)
            return
        self._commit_rows(key, idx, [
            (idx, slot, 1 if eff[0] == "asgn" else 0, dot_col or 0,
             int(seq), obs_pairs, op_dc_col, int(payload.commit_time),
             ss_pairs)])

    def seed_effects(self, state):
        # state: frozenset(((actor, seq), value)) — one un-observed
        # assign per live (dot, value) pair
        return [("asgn", v, dot, ()) for dot, v in state]

    def _device_gc(self, gst_dense):
        self.st = store.mvreg_gc(self.st, gst_dense)

    def _many_split(self, st, owned, idxs, pad, rv):
        val_lists = [self.rev_elems[i] for i in idxs]
        domain = self.domain

        def post(dots):
            actors = domain.dc_ids
            out = {}
            for i, k in enumerate(owned):
                pairs = set()
                for slot, v in enumerate(list(val_lists[i])):
                    if slot >= dots.shape[1]:
                        break  # slot grown after the capture
                    for j, s in enumerate(dots[i, slot][:len(actors)]):
                        if s > 0:
                            pairs.add(((actors[j], int(s)), v))
                out[k] = frozenset(pairs)
            return out

        return ((store.mvreg_read_keys, (st, pad, rv)), post)


class FlagEwPlane(OrsetPlane):
    """Device plane for flag_ew — an OR-Set with one implicit element
    (slot 0 holds the enable dots; crdt/flags.py FlagEW)."""

    type_name = "flag_ew"

    def __init__(self, domain, key_capacity, n_lanes, flush_ops, gc_ops,
                 max_dcs, ingest_settings=None):
        super().__init__(domain, key_capacity, n_lanes, 1, flush_ops,
                         gc_ops, max_dcs, max_slots=1,
                         ingest_settings=ingest_settings)

    def stage(self, key, payload: Payload) -> None:
        idx = self._key_idx(key)
        eff = payload.effect
        op_dc_col = self._dc_col(payload.commit_dc)
        ss_pairs = self._ss_pairs(payload.snapshot_vc)
        if op_dc_col is None or ss_pairs is None:
            self.evict(key)
            return
        if eff[0] == "en":
            _, dot, observed = eff
            actor, seq = dot
            dot_col = self._dc_col(actor)
            is_add, ok = 1, dot_col is not None
        else:  # "dis"
            _, observed = eff
            dot_col, seq, is_add, ok = 0, 0, 0, True
        obs_pairs = self._decode_obs(observed) if ok else None
        if obs_pairs is None:
            self.evict(key)
            return
        self._commit_rows(key, idx, [
            (idx, 0, is_add, dot_col or 0, int(seq), obs_pairs,
             op_dc_col, int(payload.commit_time), ss_pairs)])

    def seed_effects(self, state):
        # state: frozenset((actor, seq)) enable dots — one
        # un-observed enable per dot
        return [("en", dot, ()) for dot in state]

    def _many_split(self, st, owned, idxs, pad, rv):
        domain = self.domain

        def post(dots):
            actors = domain.dc_ids
            return {
                k: frozenset(
                    (actors[j], int(s))
                    for j, s in enumerate(dots[i, 0][:len(actors)])
                    if s > 0)
                for i, k in enumerate(owned)
            }

        return ((store.orset_read_keys, (st, pad, rv)), post)


class RwsetPlane(OrsetPlane):
    """Device plane for set_rw (remove-wins) — two dot tables with
    cross-cancellation (store.rwset_*; host oracle crdt/sets.py SetRW).
    Row tuple: (key_idx, slot, kind, dot_col, dot_seq, obs_add_pairs,
    obs_rmv_pairs, op_dc_col, op_ct, ss_pairs).

    The reconstructed state collapses each (element, plane, DC) dot set
    to its max seq.  Unlike set_aw, the host oracle's add set CAN hold
    several live dots per DC column (adds don't cancel adds), so the
    reconstruction under-reports stale older dots — *value*-exact
    nonetheless: presence needs an empty remove plane, which requires a
    fresh add dot that the collapse always retains (see the kernel doc,
    mat/kernels.py rwset_apply).  Oracle tests therefore compare at
    value level for this type.  Because of the collapse the type is in
    DevicePlane.STATE_LOSSY: downstream generation never reads this
    fold — require_state_downstream reads take an exact log replay
    (``exact_state``, an argument of the read's capture:
    PartitionManager.read_many_begin)."""

    type_name = "set_rw"
    # (slot, kind, dot_dc, dot_seq, obs_add, obs_rmv, op_dc, op_ct, op_ss)
    _row_cols = ("s", "s", "s", "s", "vv", "vv", "s", "s", "vv")
    _append_fn = staticmethod(store.rwset_append)

    def _init_state(self, key_capacity):
        return store.rwset_shard_init(
            key_capacity, self.n_lanes, self.n_slots, self.domain.d,
            dtype=jnp.int64)

    def _grow_dcs(self, new_d):
        self.st = store.rwset_grow(self.st, n_dcs=new_d)

    def _grow_keys(self, new_k):
        self.st = store.rwset_grow(self.st, n_keys=new_k)

    def _grow_slots(self, new_e):
        self.flush("grow")
        self.n_slots = new_e
        self.st = store.rwset_grow(self.st, n_slots=new_e)

    def stage(self, key, payload: Payload) -> None:
        idx = self._key_idx(key)
        kind_name, entries = payload.effect
        op_dc_col = self._dc_col(payload.commit_dc)
        ss_pairs = self._ss_pairs(payload.snapshot_vc)
        if op_dc_col is None or ss_pairs is None:
            self.evict(key)
            return
        rows = []
        for entry in entries:
            if kind_name == "add":
                elem, dot, obs_rmvs = entry
                kind, obs_adds = 0, ()
            elif kind_name == "rmv":
                elem, dot, obs_adds = entry
                kind, obs_rmvs = 1, ()
            else:  # "reset": mints nothing, cancels both planes
                elem, obs_adds, obs_rmvs = entry
                kind, dot = 2, (None, 0)
            actor, seq = dot
            dot_col = 0 if actor is None else self._dc_col(actor)
            slot = self._slot(idx, elem)
            oa = self._decode_obs(obs_adds)
            orm = self._decode_obs(obs_rmvs)
            if slot is None or oa is None or orm is None \
                    or dot_col is None:
                self.evict(key)
                return
            rows.append((idx, slot, kind, dot_col, int(seq), oa, orm,
                         op_dc_col, int(payload.commit_time), ss_pairs))
        self._commit_rows(key, idx, rows)


    def seed_effects(self, state):
        # STATE_LOSSY: the fold collapses per-DC dot sets, and a seed
        # staged from the collapsed form would under-cancel at exact
        # replicas — these keys recover host-path (log/seed replay)
        return None

    def _purge_idx(self, idx):
        self.st = store.rwset_purge_keys(
            self.st, np.asarray([idx], dtype=np.int32))
        self.elem_index[idx] = {}
        self.rev_elems[idx] = []

    def _device_gc(self, gst_dense):
        self.st = store.rwset_gc(self.st, gst_dense)

    @staticmethod
    def _dots_of(row, actors):
        return frozenset(
            (actors[j], int(s))
            for j, s in enumerate(row[:len(actors)]) if s > 0)

    def _many_split(self, st, owned, idxs, pad, rv):
        elem_lists = [self.rev_elems[i] for i in idxs]
        domain = self.domain

        def post(out_arrays):
            adds, rmvs = out_arrays
            actors = domain.dc_ids
            out = {}
            for i, k in enumerate(owned):
                state = {}
                for slot, elem in enumerate(list(elem_lists[i])):
                    if slot >= adds.shape[1]:
                        break
                    a = self._dots_of(adds[i, slot], actors)
                    r = self._dots_of(rmvs[i, slot], actors)
                    if a or r:
                        state[elem] = (a, r)
                out[k] = state
            return out

        return ((store.rwset_read_keys, (st, pad, rv)), post)


class FlagDwPlane(RwsetPlane):
    """Device plane for flag_dw — the remove-wins lattice with one
    implicit element (slot 0; crdt/flags.py FlagDW).  State tuple
    (enable_dots, disable_dots)."""

    type_name = "flag_dw"

    def __init__(self, domain, key_capacity, n_lanes, flush_ops, gc_ops,
                 max_dcs, ingest_settings=None):
        super().__init__(domain, key_capacity, n_lanes, 1, flush_ops,
                         gc_ops, max_dcs, max_slots=1,
                         ingest_settings=ingest_settings)

    def stage(self, key, payload: Payload) -> None:
        idx = self._key_idx(key)
        eff = payload.effect
        op_dc_col = self._dc_col(payload.commit_dc)
        ss_pairs = self._ss_pairs(payload.snapshot_vc)
        if op_dc_col is None or ss_pairs is None:
            self.evict(key)
            return
        if eff[0] == "en":       # enable = add-plane dot, cancels dis
            _, dot, obs_dis = eff
            kind, obs_en = 0, ()
        elif eff[0] == "dis":    # disable = rmv-plane dot, cancels en
            _, dot, obs_en = eff
            kind, obs_dis = 1, ()
        else:                    # "reset": cancels both, mints nothing
            _, obs_en, obs_dis = eff
            kind, dot = 2, (None, 0)
        actor, seq = dot
        dot_col = 0 if actor is None else self._dc_col(actor)
        oa = self._decode_obs(obs_en)
        orm = self._decode_obs(obs_dis)
        if oa is None or orm is None or dot_col is None:
            self.evict(key)
            return
        self._commit_rows(key, idx, [
            (idx, 0, kind, dot_col, int(seq), oa, orm, op_dc_col,
             int(payload.commit_time), ss_pairs)])

    def _many_split(self, st, owned, idxs, pad, rv):
        domain = self.domain

        def post(out_arrays):
            adds, rmvs = out_arrays
            actors = domain.dc_ids
            return {
                k: (self._dots_of(adds[i, 0], actors),
                    self._dots_of(rmvs[i, 0], actors))
                for i, k in enumerate(owned)
            }

        return ((store.rwset_read_keys, (st, pad, rv)), post)


class SetGoPlane(OrsetPlane):
    """Device plane for set_go — monotone presence, no dot algebra
    (store.setgo_*; host oracle crdt/sets.py SetGO).  Effect = tuple of
    elements; row tuple: (key_idx, slot, op_dc_col, op_ct, ss_pairs).
    Dot-collapse soundness is moot (no dots), so uncertified commits may
    stay on the device path (like counter_pn)."""

    type_name = "set_go"
    # (slot, op_dc, op_ct, op_ss)
    _row_cols = ("s", "s", "s", "vv")
    _append_fn = staticmethod(store.setgo_append)

    def _init_state(self, key_capacity):
        return store.setgo_shard_init(
            key_capacity, self.n_lanes, self.n_slots, self.domain.d,
            dtype=jnp.int64)

    def _grow_dcs(self, new_d):
        self.st = store.setgo_grow(self.st, n_dcs=new_d)

    def _grow_keys(self, new_k):
        self.st = store.setgo_grow(self.st, n_keys=new_k)

    def _grow_slots(self, new_e):
        self.flush("grow")
        self.n_slots = new_e
        self.st = store.setgo_grow(self.st, n_slots=new_e)

    def stage(self, key, payload: Payload) -> None:
        idx = self._key_idx(key)
        op_dc_col = self._dc_col(payload.commit_dc)
        ss_pairs = self._ss_pairs(payload.snapshot_vc)
        if op_dc_col is None or ss_pairs is None:
            self.evict(key)
            return
        rows = []
        for elem in payload.effect:
            slot = self._slot(idx, elem)
            if slot is None:
                self.evict(key)
                return
            rows.append((idx, slot, op_dc_col,
                         int(payload.commit_time), ss_pairs))
        self._commit_rows(key, idx, rows)

    def seed_effects(self, state):
        # state: frozenset(elems) — one grow-only add (one row) per
        # element, chunkable against the lane budget like set_aw's
        return [(e,) for e in state]

    def _purge_idx(self, idx):
        self.st = store.setgo_purge_keys(
            self.st, np.asarray([idx], dtype=np.int32))
        self.elem_index[idx] = {}
        self.rev_elems[idx] = []

    def _device_gc(self, gst_dense):
        self.st = store.setgo_gc(self.st, gst_dense)

    def _many_split(self, st, owned, idxs, pad, rv):
        elem_lists = [self.rev_elems[i] for i in idxs]

        def post(present):
            return {
                k: frozenset(
                    e for slot, e in enumerate(list(elem_lists[i]))
                    if slot < present.shape[1] and present[i, slot])
                for i, k in enumerate(owned)
            }

        return ((store.setgo_read_keys, (st, pad, rv)), post)


#: tiebreak packing: rank << _TIE_SHIFT | seq (seq must fit the low bits)
_TIE_SHIFT = 40
_TIE_SEQ_MAX = (1 << _TIE_SHIFT) - 1


class LwwPlane(_PlaneBase):
    """Device plane for register_lww.  Row tuple:
    (key_idx, ts, tie, val_id, op_dc_col, op_ct, ss_pairs).

    The host oracle's tiebreak is (actor string, seq) compared
    lexicographically (crdt/registers.py RegisterLWW); the device
    compares packed int64s, so the plane keeps a *sorted* actor-rank
    directory and repacks stored ties (store.lww_retie) on first sight
    of a new actor — rare, host-side, and exact."""

    type_name = "register_lww"
    # (ts, tie, val_id, op_dc, op_ct, op_ss)
    _row_cols = ("s", "s", "s", "s", "s", "vv")
    _append_fn = staticmethod(store.lww_append)

    def __init__(self, domain, key_capacity, n_lanes, flush_ops, gc_ops,
                 max_dcs, ingest_settings=None):
        #: sorted actor strings; rank = index in this list
        self.actors_sorted: List[str] = []
        self._rank: Dict[str, int] = {}
        #: interned values (value -> id, id -> value)
        self.val_index: Dict[Any, int] = {}
        self.rev_vals: List[Any] = []
        super().__init__(domain, key_capacity, n_lanes, flush_ops,
                         gc_ops, max_dcs,
                         ingest_settings=ingest_settings)

    def _init_state(self, key_capacity):
        return store.lww_shard_init(
            key_capacity, self.n_lanes, self.domain.d, dtype=jnp.int64)

    def _grow_dcs(self, new_d):
        self.st = store.lww_grow(self.st, n_dcs=new_d)

    def _grow_keys(self, new_k):
        self.st = store.lww_grow(self.st, n_keys=new_k)

    def _tie(self, actor: str, seq: int) -> Optional[int]:
        if seq > _TIE_SEQ_MAX:
            return None
        rank = self._rank.get(actor)
        if rank is None:
            self.flush("grow")  # staged rows carry old-rank ties
            new_sorted = sorted(self.actors_sorted + [actor])
            remap = np.asarray(
                [new_sorted.index(a) for a in self.actors_sorted],
                dtype=np.int64)
            if len(remap):
                self.st = store.lww_retie(self.st, remap, _TIE_SHIFT)
            self.actors_sorted = new_sorted
            self._rank = {a: i for i, a in enumerate(new_sorted)}
            rank = self._rank[actor]
        return (rank << _TIE_SHIFT) | int(seq)

    #: value-directory compaction threshold: dead interned values (every
    #: assign with a fresh payload leaves one behind) are dropped once
    #: the directory outgrows this
    _val_compact_at = 1 << 16

    def _val_id(self, v) -> Optional[int]:
        try:
            vid = self.val_index.get(v)
        except TypeError:
            return None  # unhashable value — host path
        if vid is None:
            if len(self.rev_vals) >= self._val_compact_at:
                self._compact_vals()
            vid = len(self.rev_vals)
            self.val_index[v] = vid
            self.rev_vals.append(v)
        return vid

    def _compact_vals(self) -> None:
        """Drop interned values no stored row references any more
        (superseded assigns): flush, host-scan the live val columns,
        rebuild the directory, and remap the device columns
        (store.lww_reval).  Keeps register-heavy workloads from leaking
        one value object per assign forever."""
        self.flush("grow")
        ops_val = np.asarray(self.st.ops[:, store._LVAL])
        valid = np.asarray(self.st.valid)
        bval = np.asarray(self.st.base_val)
        live = set(np.unique(ops_val[valid]).tolist())
        live.update(np.unique(bval[bval >= 0]).tolist())
        remap = np.full(len(self.rev_vals), -1, dtype=np.int64)
        new_vals: List[Any] = []
        for old in sorted(live):
            remap[old] = len(new_vals)
            new_vals.append(self.rev_vals[old])
        self.st = store.lww_reval(self.st, remap)
        self.rev_vals = new_vals
        self.val_index = {v: i for i, v in enumerate(new_vals)}
        log.debug("lww plane: value directory compacted to %d entries",
                  len(new_vals))

    def stage(self, key, payload: Payload) -> None:
        idx = self._key_idx(key)
        ts, tie_pair, v = payload.effect
        actor, seq = tie_pair
        op_dc_col = self._dc_col(payload.commit_dc)
        ss_pairs = self._ss_pairs(payload.snapshot_vc)
        tie = self._tie(str(actor), int(seq))
        vid = self._val_id(v)
        if op_dc_col is None or ss_pairs is None or tie is None \
                or vid is None:
            self.evict(key)
            return
        self._commit_rows(key, idx, [
            (idx, int(ts), tie, vid, op_dc_col,
             int(payload.commit_time), ss_pairs)])

    def seed_effects(self, state):
        # state: (ts, (actor, seq), value), or the unwritten bottom
        # (0, (), None) — which needs no op at all
        ts, tie, v = state
        return [] if not tie and v is None else [(ts, tie, v)]

    def _purge_idx(self, idx):
        self.st = store.lww_purge_keys(
            self.st, np.asarray([idx], dtype=np.int32))

    def _device_gc(self, gst_dense):
        self.st = store.lww_gc(self.st, gst_dense)

    def _many_split(self, st, owned, idxs, pad, rv):
        # actors_sorted is REPLACED wholesale on a rank repack (which
        # also repacks st under the same lock) — capturing the list here
        # keeps ranks and state consistent after the lock is released
        acts = self.actors_sorted
        vals = self.rev_vals

        def post(out_arrays):
            ts, tie, val = out_arrays
            out = {}
            for i, k in enumerate(owned):
                if val[i] < 0:
                    out[k] = (0, (), None)  # unwritten at this snapshot
                else:
                    rank = int(tie[i]) >> _TIE_SHIFT
                    seq = int(tie[i]) & _TIE_SEQ_MAX
                    out[k] = (int(ts[i]), (acts[rank], seq),
                              vals[int(val[i])])
            return out

        return ((store.lww_read_keys, (st, pad, rv)), post)


#: bottom (empty) nested states as the planes reconstruct them — used by
#: the map_rr visibility rule (entry invisible iff nested state is
#: bottom, crdt/maps.py MapRR.update)
_BOTTOM = {
    "counter_pn": 0,
    "set_aw": {},
    "set_rw": {},
    "set_go": frozenset(),
    "register_mv": frozenset(),
    "register_lww": (0, (), None),
    "flag_ew": frozenset(),
    "flag_dw": (frozenset(), frozenset()),
}


class RgaPlane(_PlaneBase):
    """Device plane for rga — one VC-aware incremental store per key
    (antidote_tpu/mat/rga_store.py: folded base + op window with full
    commit-VC lanes).

    Documents are independent trees, so unlike the slotted planes there
    is no cross-key shard array: ``self.st`` maps key index -> its
    RgaStoreState, and a read folds exactly one document.  The
    reconstruction is EXACT host-oracle state — ``(uid, elem, visible)``
    tuples in RGA order including tombstones (crdt/rga.py) — so value
    reads AND downstream generation (positions over visible vertices,
    lamport max) are served from the device; rga is therefore NOT in
    STATE_LOSSY.

    Host directories per key: actor strings intern into the uid's
    ``actor_bits`` field (ids from 1; 0 is the root sentinel), elements
    into int32 ids.  A key evicts to the host path when its actors
    exceed 2^bits - 1 or a lamport would overflow the packed-uid width
    (reference materializer serves every type through one path,
    src/materializer_vnode.erl:56-110 — eviction is this plane's
    capacity escape hatch, like the slotted planes')."""

    type_name = "rga"

    def __init__(self, domain, key_capacity, flush_ops, gc_ops, max_dcs,
                 pb: int = 256, nw: int = 256, md: int = 64,
                 actor_bits: int = 8, ingest_settings=None):
        self.pb0, self.nw0, self.md0 = pb, nw, md
        self.actor_bits = actor_bits
        self._max_lam = 1 << (31 - actor_bits)
        #: per-key interning (index-aligned with rev_keys)
        self.actor_index: List[dict] = []
        self.rev_actors: List[list] = []
        self.elem_index: List[dict] = []
        self.rev_elems: List[list] = []
        super().__init__(domain, key_capacity, 1, flush_ops, gc_ops,
                         max_dcs, ingest_settings=ingest_settings)

    # -- storage hooks ------------------------------------------------------

    def _init_state(self, key_capacity):
        return {}  # key idx -> RgaStoreState

    def _grow_keys(self, new_k):
        pass  # dict-backed: nothing to repack

    def _grow_dcs(self, new_d):
        from antidote_tpu.mat import rga_store

        self.st = {i: rga_store.rga_grow(s, n_dcs=new_d)
                   for i, s in self.st.items()}

    def _key_idx(self, key):
        idx = self.key_index.get(key)
        if idx is None:
            from antidote_tpu.mat import rga_store

            idx = len(self.rev_keys)
            self.key_index[key] = idx
            self.rev_keys.append(key)
            self.actor_index.append({})
            self.rev_actors.append([])
            self.elem_index.append({})
            self.rev_elems.append([])
            self.st[idx] = rga_store.rga_store_init(
                self.pb0, self.nw0, self.md0, n_dcs=self.domain.d,
                actor_bits=self.actor_bits)
        return idx

    def _purge_idx(self, idx):
        self.st.pop(idx, None)
        self.actor_index[idx] = {}
        self.rev_actors[idx] = []
        self.elem_index[idx] = {}
        self.rev_elems[idx] = []

    # -- interning ----------------------------------------------------------

    def _actor_id(self, idx, actor) -> Optional[int]:
        """Interned actor id, kept in ACTOR-STRING order: sibling order
        is packed-uid-desc and the host oracle breaks lamport ties by
        the actor string, so ids must sort like the strings or replicas
        interning in different arrival orders diverge on concurrent
        same-lamport inserts (caught by the chaos suite).  An
        out-of-order arrival re-interns and remaps the document
        (rga_store.rga_remap_actors)."""
        d = self.actor_index[idx]
        a = d.get(actor)
        if a is not None:
            return a
        if len(d) >= (1 << self.actor_bits) - 1:
            return None  # uid width exhausted — evict
        rev = self.rev_actors[idx]
        if not rev or actor > rev[-1]:
            a = len(d) + 1
            d[actor] = a
            rev.append(actor)
            return a
        # re-intern in sorted order and remap the device state + any
        # staged rows of this key
        from antidote_tpu.mat import rga_store

        new_rev = sorted(rev + [actor])
        perm = np.zeros(1 << self.actor_bits, dtype=np.int32)
        new_ids = {s: i + 1 for i, s in enumerate(new_rev)}
        for s, old in d.items():
            perm[old] = new_ids[s]
        self.actor_index[idx] = new_ids
        self.rev_actors[idx] = new_rev
        st = self.st.get(idx)
        if st is not None:
            self.st[idx] = rga_store.rga_remap_actors(st, perm)
        remapped = []
        for r in self.rows:
            if r[0] == idx:
                r = (r[0], r[1], r[2], int(perm[r[3]]), r[4],
                     int(perm[r[5]]), *r[6:])
            remapped.append(r)
        self.rows = remapped
        return new_ids[actor]

    def _elem_id(self, idx, elem) -> int:
        d = self.elem_index[idx]
        e = d.get(elem)
        if e is None:
            e = len(self.rev_elems[idx])
            d[elem] = e
            self.rev_elems[idx].append(elem)
        return e

    # -- write path ---------------------------------------------------------

    def stage(self, key, payload: Payload) -> None:
        idx = self._key_idx(key)
        eff = payload.effect
        op_dc_col = self._dc_col(payload.commit_dc)
        ss_pairs = self._ss_pairs(payload.snapshot_vc)
        if op_dc_col is None or ss_pairs is None:
            self.evict(key)
            return
        if eff[0] == "ins":
            _, uid, ref, elem = eff
            lam, actor = uid
            rlam, ract_raw = (0, 0) if ref == (0, "") else ref
            act = self._actor_id(idx, actor)
            ract = 0 if rlam == 0 and ract_raw == 0 \
                else self._actor_id(idx, ract_raw)
            if act is None or ract is None \
                    or lam >= self._max_lam or rlam >= self._max_lam:
                self.evict(key)
                return
            row = (idx, 0, int(lam), act, int(rlam), ract,
                   self._elem_id(idx, elem), op_dc_col,
                   int(payload.commit_time), ss_pairs)
        elif eff[0] == "rm":
            _, uid = eff
            lam, actor = uid
            act = self._actor_id(idx, actor)
            if act is None or lam >= self._max_lam:
                self.evict(key)
                return
            row = (idx, 1, int(lam), act, 0, 0, 0, op_dc_col,
                   int(payload.commit_time), ss_pairs)
        else:
            self.evict(key)
            return
        self._commit_rows(key, idx, [row])

    def _append_rows(self, rows: List[tuple]) -> np.ndarray:
        """Per-key grouped append into each document's window; a full
        window folds at the newest stable horizon and/or grows — this
        plane's appends never report overflow (capacity misses evict at
        stage time)."""
        from antidote_tpu.mat import rga_store

        overflow = np.zeros(len(rows), dtype=bool)
        by_idx: Dict[int, list] = {}
        for r in rows:
            by_idx.setdefault(r[0], []).append(r)
        d = self.domain.d
        for idx, group in by_idx.items():
            st = self.st.get(idx)
            if st is None:
                continue  # evicted while staged; log replay covers it
            ins = [r for r in group if r[1] == 0]
            dels = [r for r in group if r[1] == 1]

            def col(rs, j, dt=np.int32):
                return np.asarray([r[j] for r in rs], dtype=dt)

            def ss(rs):
                m = np.zeros((len(rs), d), dtype=np.int64)
                for i, r in enumerate(rs):
                    for c, t in r[9]:
                        m[i, c] = max(m[i, c], t)
                return m

            # bucketed append: per-commit group sizes vary freely, and
            # un-padded blocks would mint one XLA program per distinct
            # (inserts, deletes) pair.  The coalesced form uploads the
            # whole block as ONE packed tensor (mat/ingest.py economy);
            # the legacy per-column form stays as the baseline knob.
            append = (rga_store.rga_append_coalesced
                      if self._ingest.enabled
                      else rga_store.rga_append_padded)
            ins_cols = (col(ins, 2), col(ins, 3), col(ins, 4),
                        col(ins, 5), col(ins, 6), col(ins, 7),
                        col(ins, 8, np.int64), ss(ins))
            del_cols = (col(dels, 2), col(dels, 3), col(dels, 7),
                        col(dels, 8, np.int64), ss(dels))
            st, ok = append(st, ins_cols, del_cols)
            if not bool(ok):
                # fold what is stable, then grow to fit the backlog
                if self._last_stable is not None:
                    pairs = self._ss_pairs(self._last_stable)
                    if pairs is not None:
                        st = rga_store.rga_fold_host(
                            st, self._dense_vc(pairs))
                        # the physical base advanced: reads below this
                        # horizon must take the log-replay path from now
                        # on (_read_vc_dense checks _base_vc)
                        self._base_vc = self._base_vc.join(
                            self._last_stable)
                        self._has_base = True
                # room for the PADDED block (the append refuses when
                # the pad would overhang, see rga_append)
                need_w = int(st.wn) + rga_store._append_bucket(len(ins))
                need_d = int(st.dn) + rga_store._append_bucket(len(dels))
                nw = st.nw
                while nw < need_w:
                    nw *= 2
                md = st.md
                while md < need_d:
                    md *= 2
                st = rga_store.rga_grow(st, nw=nw, md=md)
                st, ok = append(st, ins_cols, del_cols)
                assert bool(ok), "rga append must fit after grow"
            self.st[idx] = st
        return overflow

    def _device_gc(self, gst_dense):
        from antidote_tpu.mat import rga_store

        for idx, st in list(self.st.items()):
            if int(st.wn) == 0 and int(st.dn) == 0:
                continue  # quiescent document: nothing to fold
            self.st[idx] = rga_store.rga_fold_host(st, gst_dense)

    # -- read path ----------------------------------------------------------

    def _reader(self, st, idx, rv):
        """Closure folding document ``idx`` of the captured state at
        dense snapshot ``rv`` — this plane's ONLY reader: documents are
        independent trees with no cross-key fold, so ``_many_reader``
        is one of these a key and :meth:`read` a batch of one."""
        from antidote_tpu.mat import rga_store

        sti = st[idx]
        actors = list(self.rev_actors[idx])
        elems = list(self.rev_elems[idx])

        def run():
            lam, act, elem, vis, n = rga_store.rga_read(sti, rv)
            lam = np.asarray(lam)
            act = np.asarray(act)
            elem = np.asarray(elem)
            vis = np.asarray(vis)
            n = int(n)
            # present vertices sort to the front in document order
            return tuple(
                ((int(lam[i]), actors[int(act[i]) - 1]),
                 elems[int(elem[i])], bool(vis[i]))
                for i in range(n))

        return run

    def _many_reader(self, st, owned, idxs, pad, rv):
        """Documents fold one device call each (independent trees — no
        cross-key batching), so the base capture's padded indices go
        unused and the batch is a reader per owned key, with no
        ``.split`` to fuse."""
        readers = [(k, self._reader(st, int(i), rv))
                   for k, i in zip(owned, idxs)]

        def run():
            return {k: r() for k, r in readers}

        return run


class MapPlane:
    """Field-composite device plane for map_go / map_rr.

    A map effect is a bag of nested effects keyed by ``key_t = (field,
    nested_type)`` (crdt/maps.py; reference antidote_crdt_map_rr
    semantics).  Each nested effect routes to a PRIVATE sub-plane of the
    nested type under the synthetic key ``(map_key, key_t)`` — the map
    rides the existing per-type ring/fold/GC machinery instead of
    needing its own kernels.  Reads fan back out: one batched sub-fold
    per nested type reassembles ``{key_t: nested_state}``.

    Visibility: map_go entries exist from their first update onward, a
    snapshot-dependent fact tracked by a private set_go presence plane
    over fields; map_rr entries are visible iff the nested state is not
    bottom (MapRR.update pops bottoms), checked on the reconstructed
    state.

    Fallback is map-granular: any capacity miss in any sub-plane evicts
    the WHOLE map key to the host path (log replay of the map's effects
    rebuilds it there — synthetic keys never appear in the log).  Nested
    types without a device plane (maps-in-maps, counter_fat, counter_b)
    evict the same way."""

    SUPPORTED = frozenset(_BOTTOM)

    def __init__(self, type_name: str, make_sub,
                 make_presence=None):
        self.type_name = type_name
        self._make_sub = make_sub
        self._subs: Dict[str, _PlaneBase] = {}
        self._presence = make_presence() if make_presence else None
        if self._presence is not None:
            self._presence._in_map = True
            self._presence.on_evict = \
                lambda mkey, t, state=None: self._presence_evicted(
                    mkey, state)
        #: map_key -> set of key_t ever staged on device.  Doubles as
        #: the plane's key directory (``key_index`` below) so operator
        #: surfaces can treat every plane uniformly.
        self.fields: Dict[Any, set] = {}
        self.pending_keys: set = set()
        self.on_evict: Callable[..., None] = \
            lambda k, t, state=None: None
        #: unlogged-eviction flags (see _PlaneBase): the MAP exports
        #: the reassembled state; sub-planes only get the emergency-
        #: fold behavior (no_log_replay, propagated at creation)
        self.evict_export = False
        self.no_log_replay = False
        self._exporting: set = set()
        #: set by a mid-decode eviction inside :meth:`stage`: the entry
        #: subset the export could not cover (see _set_stage_residual)
        self.stage_residual = None
        #: (key_t, state) of the sub whose eviction triggered ours —
        #: that sub's rows purged before our export ran (see
        #: _sub_evicted)
        self._evict_overlay = None
        #: (mkey, visible-set) when the PRESENCE plane's eviction
        #: triggered ours — its pre-purge fold replaces the export's
        #: visibility filter (see _presence_evicted)
        self._presence_vis_override = None
        self._evicting = None
        self._warm_kicked = False

    def kick_warm(self) -> None:
        """First-use warm trigger: existing sub-planes (presence
        included) warm-compile now, and every LAZILY created sub-plane
        warms at creation (see _PlaneBase.warm_appends)."""
        if self._warm_kicked:
            return
        self._warm_kicked = True
        orig = self._make_sub

        def warming_make(tn, _orig=orig):
            sub = _orig(tn)
            sub.warm_appends()
            return sub

        self._make_sub = warming_make
        for s in self._all_planes():
            s.warm_appends()

    # -- plumbing shared with _PlaneBase's interface ------------------------

    @property
    def rows(self):
        out = []
        for s in self._all_planes():
            out.extend(s.rows)
        return out

    def _all_planes(self):
        planes = list(self._subs.values())
        if self._presence is not None:
            planes.append(self._presence)
        return planes

    def owns(self, key) -> bool:
        return key in self.fields

    def flight_blocks(self, keys) -> bool:
        """Never: a map's sub-planes flush in one hold (split_flush)."""
        return False

    @property
    def key_index(self) -> Dict[Any, set]:
        """Key directory (uniform with _PlaneBase.key_index: len() =
        device-resident keys, ``in`` = ownership)."""
        return self.fields

    def _sub(self, ntype: str) -> _PlaneBase:
        sub = self._subs.get(ntype)
        if sub is None:
            sub = self._make_sub(ntype)
            sub._in_map = True
            sub.on_evict = \
                lambda skey, t, state=None: self._sub_evicted(
                    skey, state)
            sub.no_log_replay = self.no_log_replay
            sub.evict_export = self.evict_export
            self._subs[ntype] = sub
        return sub

    def _presence_evicted(self, mkey, state=None) -> None:
        if self._evicting == mkey:
            return  # our own purge loop
        # the presence plane purged its rows BEFORE this map-level
        # eviction can export, so the export's visibility filter would
        # see an empty set and seed the host with {} (the zeroing bug,
        # presence flavor): its own pre-purge export — the visibility
        # SET — rides along and replaces the filter (unlogged mode)
        self._presence_vis_override = (mkey, state) \
            if state is not None else None
        try:
            self.evict(mkey)
        finally:
            self._presence_vis_override = None

    def _sub_evicted(self, skey, state=None) -> None:
        mkey, key_t = skey
        if self._evicting == mkey:
            return  # our own purge loop
        # the triggering sub purged its rows BEFORE this map-level
        # eviction can export — its own pre-purge export (``state``)
        # is the only copy of that field's history; overlay it onto
        # the map export (unlogged mode)
        self._evict_overlay = (key_t, state) \
            if key_t is not None and state is not None else None
        try:
            self.evict(mkey)
        finally:
            self._evict_overlay = None

    # -- write path ---------------------------------------------------------

    def _note_staged_vc(self, payload: Payload) -> None:
        """Top-level no-op (sub-planes track their own bounds at
        :meth:`stage`, where the nested payloads are built)."""

    def stage(self, key, payload: Payload) -> None:
        """Decode one committed map effect into sub-plane stages; evicts
        the whole map on any nested capacity miss.

        ``stage_residual`` (consumed by DevicePlane.stage in unlogged
        mode): when the eviction fires MID-decode, some of this op's
        sub-entries were already staged and may be VISIBLE in the
        eviction's exported state (map_rr: every staged field; map_go:
        only fields that existed before this op — a new field's
        presence rows stage last and were dropped) — re-applying the
        FULL effect onto the seed would double-apply those.  The
        residual is the entry subset the export could not have
        covered."""
        _kind, entries = payload.effect
        pre_fields = set(self.fields.get(key, ()))
        # register the key BEFORE any reject so evict() always runs the
        # migration (the op is already in the log, like _PlaneBase.stage)
        self.fields.setdefault(key, set())
        self.stage_residual = None
        if any(kt[1] not in self.SUPPORTED for kt, _ in entries):
            self.evict(key)           # nested map / counter_fat / b
            self.stage_residual = payload.effect  # nothing staged
            return
        staged = []
        for key_t, neff in entries:
            sub = self._sub(key_t[1])
            skey = (key, key_t)
            sub_payload = dc_replace(
                payload, key=skey, type_name=key_t[1], effect=neff)
            sub._note_staged_vc(sub_payload)
            sub.stage(skey, sub_payload)
            if key not in self.fields:
                # a sub capacity miss evicted us mid-decode
                self._set_stage_residual(_kind, entries, staged,
                                         pre_fields)
                return
            self.fields[key].add(key_t)
            staged.append(key_t)
        if self._presence is not None and staged:
            pres_payload = dc_replace(
                payload, type_name="set_go", effect=tuple(staged))
            self._presence._note_staged_vc(pres_payload)
            self._presence.stage(key, pres_payload)
            if key not in self.fields:
                self._set_stage_residual(_kind, entries, staged,
                                         pre_fields)
                return
        self.pending_keys.add(key)

    def _set_stage_residual(self, kind, entries, staged,
                            pre_fields) -> None:
        """Entries of the current effect the mid-decode eviction's
        export could NOT include: everything except fields both staged
        AND visible at export time (see :meth:`stage`)."""
        visible = set(staged) & pre_fields \
            if self._presence is not None else set(staged)
        residual = tuple(e for e in entries if e[0] not in visible)
        self.stage_residual = (kind, residual) if residual else None

    _schedule = None

    def maybe_flush_gc(self, stable_vc: Optional[VC]) -> None:
        for p in self._all_planes():
            p._schedule = self._schedule  # async-flush wiring follows
            p.maybe_flush_gc(stable_vc)
        if not any(p.rows for p in self._all_planes()):
            self.pending_keys.clear()

    def flush(self, kind: str = "explicit") -> None:
        for p in self._all_planes():
            p.flush(kind)
        self.pending_keys.clear()

    def gc(self, stable_vc: VC) -> None:
        for p in self._all_planes():
            p.gc(stable_vc)

    def _export_evict_state(self, key):
        """The reassembled map state, captured BEFORE the sub purges,
        when there is no log to replay (see _PlaneBase).  A sub whose
        own eviction triggered ours already purged its rows — its
        pre-purge export rides in ``_evict_overlay`` and replaces that
        field here."""
        if not self.evict_export or key in self._exporting:
            return None
        self._exporting.add(key)
        try:
            if self._presence_vis_override is not None \
                    and self._presence_vis_override[0] == key:
                # the presence plane already purged: the normal read
                # would filter every field against an empty visibility
                # set — assemble from the (intact) sub planes and the
                # presence's own pre-purge fold instead
                vis = self._presence_vis_override[1] or frozenset()
                state = {}
                for key_t in self.fields.get(key, ()):
                    if key_t not in vis:
                        continue
                    sub = self._subs.get(key_t[1])
                    if sub is not None:
                        state[key_t] = sub.read((key, key_t), None)
            else:
                state = self.read(key, None)
        except Exception:  # noqa: BLE001 — export must not break evict
            log.exception(
                "map evict-state export failed for %r (%s)",
                key, self.type_name)
            return None
        finally:
            self._exporting.discard(key)
        if self._evict_overlay is not None and isinstance(state, dict):
            key_t, sub_state = self._evict_overlay
            state = dict(state)
            state[key_t] = sub_state
        return state

    def evict(self, key) -> None:
        """Purge every synthetic key of the map and hand its history to
        the host path (on_evict replays the map's log records; with no
        log, the pre-purge reassembled state travels along)."""
        if key not in self.fields:
            return
        state = self._export_evict_state(key)
        self._evicting = key
        try:
            for key_t in self.fields.pop(key, ()):
                sub = self._subs.get(key_t[1])
                if sub is not None:
                    # our own purge: the map already exported; a per-
                    # field export here would be O(fields) wasted folds
                    prev = sub.evict_export
                    sub.evict_export = False
                    try:
                        sub.evict((key, key_t))
                    finally:
                        sub.evict_export = prev
            if self._presence is not None:
                prev = self._presence.evict_export
                self._presence.evict_export = False  # see sub note
                try:
                    self._presence.evict(key)
                finally:
                    self._presence.evict_export = prev
        finally:
            self._evicting = None
        self.pending_keys.discard(key)
        log.debug("device plane: evicted %r (%s)", key, self.type_name)
        self.on_evict(key, self.type_name, state)

    # -- read path ----------------------------------------------------------

    def read_many_begin(self, keys: list, read_vc: Optional[VC]):
        """Lock-held capture (see _PlaneBase.read_many_begin): synthetic
        keys of ALL requested maps are grouped so each nested type
        costs ONE batched sub-fold (plus one presence fold for map_go)
        regardless of how many maps the transaction reads — the same
        one-fold-per-type batching the flat planes get.  The closure
        reassembles per-map states outside the lock."""
        owned = [k for k in keys if k in self.fields]
        if not owned:
            return dict

        def group(ks):
            bt: Dict[str, list] = {}
            for k in ks:
                for kt in self.fields[k]:
                    bt.setdefault(kt[1], []).append((k, kt))
            return bt

        # Pre-flush BEFORE any capture: a flush inside a sub-capture
        # could overflow -> evict the map -> purge SIBLING subs, which
        # deletes (donated) arrays already captured for an earlier type.
        # After this loop the captures below cannot trigger a flush.
        for ntype, pairs in group(owned).items():
            sub = self._sub(ntype)
            if not sub.pending_keys.isdisjoint(pairs):
                sub.flush("read")
        if self._presence is not None and not \
                self._presence.pending_keys.isdisjoint(owned):
            self._presence.flush("read")
        owned = [k for k in owned if k in self.fields]  # flush may evict
        if not owned:
            return dict
        parts = []
        for ntype, pairs in group(owned).items():
            parts.append((pairs,
                          self._sub(ntype).read_many_begin(pairs, read_vc)))
        pres = (self._presence.read_many_begin(owned, read_vc)
                if self._presence is not None else None)

        def run():
            states: Dict[Any, dict] = {k: {} for k in owned}
            for pairs, cl in parts:
                got = cl()
                for k, kt in pairs:
                    ns = got.get((k, kt))
                    if ns is None:
                        continue
                    if pres is None and ns == _BOTTOM[kt[1]]:
                        continue      # map_rr: bottom => invisible
                    states[k][kt] = ns
            if pres is not None:
                vis = pres()
                for k in owned:
                    v = vis.get(k, frozenset())
                    states[k] = {kt: ns for kt, ns in states[k].items()
                                 if kt in v}
            return states

        return run

    def read_many(self, keys: list, read_vc: Optional[VC]) -> dict:
        return self.read_many_begin(keys, read_vc)()

    def read(self, key, read_vc: Optional[VC]):
        """Map host state ({(field, nested_type): nested_state}) at
        ``read_vc``: the batched read with one map in it, and
        ReadBelowBase where the map is not on the device — the flat
        planes' contract, in their words."""
        return _PlaneBase.read(self, key, read_vc)


class DevicePlane:
    """Per-partition facade over the type planes; all calls run under
    the owning PartitionManager's lock (one-writer discipline, like the
    reference's single vnode process)."""

    def __init__(self, config=None, key_capacity: int = 1024,
                 n_lanes: int = 8, n_slots: int = 8,
                 flush_ops: int = 256, gc_ops: int = 2048,
                 max_dcs: int = 64, max_slots: int = 256,
                 ingest_settings: Optional[ingest.IngestSettings] = None):
        if config is not None:
            key_capacity = config.device_key_capacity
            n_lanes = config.device_lanes
            n_slots = config.device_slots
            flush_ops = config.device_flush_ops
            gc_ops = config.device_gc_ops
            max_dcs = config.device_max_dcs
            max_slots = config.device_max_slots
            # the ONE ingest factory (mat/ingest.py): the sharded
            # stores build their settings from the same call, so the
            # single-shard and mesh assemblies honor the same knobs
            ingest_settings = ingest.ingest_from_config(config)
        ing = ingest_settings or ingest.ingest_from_config(None)
        slotted = {"set_aw": OrsetPlane, "register_mv": MvregPlane,
                   "set_rw": RwsetPlane, "set_go": SetGoPlane}
        flat = {"counter_pn": CounterPlane, "register_lww": LwwPlane,
                "flag_ew": FlagEwPlane, "flag_dw": FlagDwPlane}

        def make(tn: str):
            """Fresh plane instance for a type (top level, or a map's
            private sub-plane)."""
            if tn in slotted:
                return slotted[tn](ClockDomain(8), key_capacity, n_lanes,
                                   n_slots, flush_ops, gc_ops, max_dcs,
                                   max_slots, ingest_settings=ing)
            return flat[tn](ClockDomain(8), key_capacity, n_lanes,
                            flush_ops, gc_ops, max_dcs,
                            ingest_settings=ing)

        self.planes: Dict[str, Any] = {
            tn: make(tn) for tn in (*slotted, *flat)}
        self.planes["map_go"] = MapPlane(
            "map_go", make, make_presence=lambda: make("set_go"))
        self.planes["map_rr"] = MapPlane("map_rr", make)
        self.planes["rga"] = RgaPlane(
            ClockDomain(8), key_capacity, flush_ops, gc_ops, max_dcs,
            ingest_settings=ing)
        #: mesh device this partition's plane states are committed to
        #: (None = default device); see place_on
        self.device = None
        #: jax.sharding.Mesh the plane states are GSPMD-sharded over
        #: (None = single-chip); see place_sharded.  Mutually exclusive
        #: with ``device`` — a plane is pinned to ONE chip or sharded
        #: over all of them, never both.
        self.mesh = None
        #: when set (by the owning PartitionManager), threshold flushes
        #: and GCs are SCHEDULED here instead of running inline on the
        #: committing transaction's back — group commit: the commit
        #: path only stages; the XLA work happens on the flusher thread
        #: under the partition lock (reads needing pending data still
        #: flush inline — they need the result)
        self.flush_scheduler = None
        #: keys evicted to the host path (sticky)
        self.host_only: set = set()
        #: no-log-to-replay mode (set by set_evict_handler): evictions
        #: export state and decode-reject ops bounce back to the caller
        self._evict_export = False
        #: types whose dense representation collapses dot sets per DC —
        #: only sound under write-write certification (module doc).
        #: counter_pn and set_go mint no dots and are exempt.
        #: counter_fat stays host-served entirely: its value is a SUM
        #: over live dots, so the per-column collapse cannot reproduce
        #: the exact per-dot state a reset's downstream generation
        #: needs (a lossy observed list would under-cancel at exact
        #: replicas — a value divergence, not just a representation
        #: one).  The ambiguity is pinned by oracle tests: two
        #: histories with identical per-column collapse give different
        #: values under the same prefix reset
        #: (tests/unit/test_counter_fat_collapse.py).  Maps count as
        #: dot-collapsing because their nested
        #: entries may (conservative for an all-counter map_go).
        self.dot_collapse_types = frozenset(
            {"set_aw", "register_mv", "flag_ew", "set_rw", "flag_dw",
             "map_go", "map_rr"})

    #: types whose HOST state can hold several live dots per
    #: (element, plane, DC) column — their update has no self-supersede
    #: (crdt/sets.py SetRW.update does ``adds | {dot}``) — so the device
    #: fold's per-column max-seq collapse is value-exact but NOT
    #: state-exact.  An effect generated from the collapsed state lists
    #: only the newest observed dot and under-cancels at exact replicas
    #: (permanent divergence); set_aw / register_mv / flag_ew are immune
    #: because their ops supersede every observed same-column dot.
    STATE_LOSSY = frozenset({"set_rw", "flag_dw"})

    def state_exact(self, type_name: str, key) -> bool:
        """True iff the device fold reconstructs this key's EXACT host
        state, safe to feed downstream generation
        (require_state_downstream reads, reference call site
        src/clocksi_downstream.erl:43-67).  Maps are exact iff no
        device-resident field has a lossy nested type."""
        if type_name in ("map_go", "map_rr"):
            flds = self.planes[type_name].fields.get(key)
            return flds is None or all(
                kt[1] not in self.STATE_LOSSY for kt in flds)
        return type_name not in self.STATE_LOSSY

    def place_on(self, device) -> None:
        """Commit every plane's state arrays to ``device`` — the ring as
        the live data plane across a host's chips: partition p's
        materializer lives on chip p % n (the reference instantiates
        every vnode layer per partition across nodes,
        src/antidote_app.erl:42-59; per-partition device placement is
        the same idea over the mesh).  JAX's committed-placement rule
        keeps every functional update (append/gc/grow return NEW
        arrays from committed inputs) on the same chip, so one call at
        partition build time pins the plane for its lifetime.  RGA
        documents (dict-of-states, created lazily per document) keep
        default placement."""
        def _pin(p):
            p._device = device  # where a grow puts the state back
            p.st = jax.device_put(p.st, device)

        def _place(plane):
            if isinstance(plane, MapPlane):
                orig = plane._make_sub

                def placed_make(tn, _orig=orig):
                    sub = _orig(tn)
                    _pin(sub)
                    return sub

                plane._make_sub = placed_make
                for s in plane._all_planes():
                    _pin(s)
            elif isinstance(plane, RgaPlane):
                pass  # per-document dict states: lazily created
            else:
                _pin(plane)

        self.device = device
        for plane in self.planes.values():
            _place(plane)

    def place_sharded(self, mesh) -> None:
        """Shard every plane's state arrays over ``mesh`` per the named
        partition rules (mat/sharded.py PARTITION_RULES) — the pod-
        scale materializer: the key axis splits across chips, clock-
        domain directories replicate, and every subsequent dispatch on
        the state is ONE multi-chip GSPMD program serialized under
        runtime.COLLECTIVE_LOCK (_PlaneBase._collective_cm).  Each
        plane also gets a per-shard residency router (ShardRouter):
        evictions charge only the OWNING shard's overflow economy, so
        one hot shard spilling cannot stop the other shards' keys from
        staying device-resident.  RGA documents (host-side dict of
        per-document trees) keep default placement, exactly like
        place_on."""
        from antidote_tpu.mat import sharded as _sharded

        n_shards = int(mesh.shape["part"])

        def _wire(p):
            p._mesh = mesh
            p._router = _sharded.ShardRouter(n_shards)
            p.st = _sharded.place_state(mesh, p.st)

        def _place(plane):
            if isinstance(plane, MapPlane):
                orig = plane._make_sub

                def sharded_make(tn, _orig=orig):
                    sub = _orig(tn)
                    _wire(sub)
                    return sub

                plane._make_sub = sharded_make
                for s in plane._all_planes():
                    _wire(s)
            elif isinstance(plane, RgaPlane):
                pass  # per-document dict states: host-side, unsharded
            else:
                _wire(plane)

        self.mesh = mesh
        for plane in self.planes.values():
            _place(plane)

    def refresh_shard_stats(self) -> None:
        """Publish the SHARD_* residency families (stats.py): per-shard
        device-resident key counts across all sharded planes, plus the
        device-resident percentage the config18 bench gates on
        (resident keys vs resident + host-evicted)."""
        if self.mesh is None:
            return
        n_shards = int(self.mesh.shape["part"])
        per_shard = [0] * n_shards
        resident = 0

        def _count(p):
            nonlocal resident
            r = p._router
            if r is None:
                return
            for idx, k in enumerate(p.rev_keys):
                if k is _Evicted:
                    continue
                per_shard[r.shard_of(idx, p.capacity)] += 1
                resident += 1

        for plane in self.planes.values():
            if isinstance(plane, MapPlane):
                for s in plane._all_planes():
                    _count(s)
            elif not isinstance(plane, RgaPlane):
                _count(plane)
        for s, n in enumerate(per_shard):
            stats.registry.shard_resident_keys.set(n, shard=str(s))
        total = resident + len(self.host_only)
        if total:
            stats.registry.shard_device_resident_pct.set(
                100.0 * resident / total)

    def set_evict_handler(self, fn: Callable[..., None],
                          export_state: bool = False) -> None:
        """Wire the eviction migration.  ``export_state=True`` marks a
        partition with NO durable log: evictions then materialize the
        key's state from the device fold before purging (the handler
        receives it as ``state``) instead of replaying an empty log —
        the PR-7-flagged silent-zeroing fix."""
        def handler(key, type_name, state=None):
            self.host_only.add(key)
            fn(key, type_name, state)
        self._evict_export = export_state
        for p in self.planes.values():
            p.on_evict = handler
            p.evict_export = export_state
            p.no_log_replay = export_state
            if isinstance(p, MapPlane):
                for s in p._all_planes():
                    s.no_log_replay = export_state
                # subs export too: a sub-triggered map eviction purges
                # the sub BEFORE the map-level export, and the sub's
                # own pre-purge export is that field's only copy; the
                # presence plane likewise (its fold IS the visibility
                # set the map export filters by)
                for s in p._subs.values():
                    s.evict_export = export_state
                if p._presence is not None:
                    p._presence.evict_export = export_state

    def set_settle_notify(self, fn: Callable[[], None]) -> None:
        """Wire what a settling flight calls under the partition lock:
        the owner's ``notify_all``, for the threads waiting for it
        (planes that never fly — maps, RGA — have no use for it)."""
        for p in self.planes.values():
            if isinstance(p, _PlaneBase):
                p.on_settled = fn

    def flying(self) -> bool:
        """Whether any type plane has a flight out (under the partition
        lock)."""
        return any(getattr(p, "_flight", None) is not None
                   for p in self.planes.values())

    def accepts(self, type_name: str, key) -> bool:
        if type_name not in self.planes or key in self.host_only:
            return False
        p = self.planes[type_name]
        r = getattr(p, "_router", None)
        if r is not None and key not in p.key_index:
            # per-shard adaptive admission: a NEW key would land at
            # the next directory index — if that index's owning shard
            # overflowed since the last fold, route the key host-side
            # instead of feeding a ring that will evict it right back
            return r.admits(len(p.rev_keys), p.capacity)
        return True

    def owns(self, type_name: str, key) -> bool:
        p = self.planes.get(type_name)
        return p is not None and p.owns(key)

    def seed_state(self, key, type_name: str, state, vc) -> bool:
        """Install a checkpoint seed as DEVICE-resident base state
        (ISSUE 13): decode the folded ``state`` back into plane rows
        via the type's own effect decoder (``seed_effects`` — the
        inverse of the evict/export fold, which already proves the
        state round-trips) and stage them like any committed op; the
        caller folds the staged rows into the device base at the seed
        clock (``gc``), so base VC = seed frontier and a read below it
        replay-gates to the log path exactly like
        ``HostStore.seed_state``.  The synthetic payload's commit VC
        is ``vc`` itself (snapshot = vc, commit entry drawn from it),
        so any read covering the frontier includes every seed row.

        Returns False — caller seeds the host path instead — when the
        type has no state→effect decoding (maps, RGA, STATE_LOSSY
        collapses), the key is already host-pinned, or a capacity miss
        evicted it mid-seed (the eviction's migration already host-
        seeded it from the checkpoint)."""
        p = self.planes.get(type_name)
        seed_fx = getattr(p, "seed_effects", None)
        if p is None or seed_fx is None or not self.accepts(
                type_name, key) or not vc:
            return False
        effs = seed_fx(state)
        if effs is None:
            return False
        if not p._warm_kicked:
            p.kick_warm()
        tracer.instant("ckpt_seed_device", "device", key=key,
                       type=type_name, effects=len(effs))
        # commit VC == the seed frontier exactly: snapshot_vc carries
        # the whole frontier and the commit entry is one of its own
        # components, so the join adds nothing
        dc, ct = max(vc.items(), key=lambda kv: kv[1])
        # intern the frontier's DC columns UP FRONT, before any state
        # lands: the caller's per-plane base fold (gc at the seed-
        # clock join) relies on every accepted seed's frontier being
        # internable — a bottom-state seed stages NO rows, so without
        # this check it could smuggle an un-internable DC into the
        # join, the fold's _ss_pairs would miss, and every seed in
        # the plane would be left un-gated (served un-replayed below
        # its frontier).  A frontier past the column capacity routes
        # host-path like any other capacity miss.
        if p._ss_pairs(VC(vc)) is None:
            return False
        p._key_idx(key)  # intern even a bottom-state seed (owns()=True)
        # chunk against the per-key lane budget: a dot-heavy key's
        # rows would overflow its ring lanes in one batch, and at boot
        # there is no stable horizon for the overflow-retry fold —
        # fold the staged chunk into the base at the seed frontier
        # (its exact commit VC) and keep going
        lanes = max(int(getattr(p, "n_lanes", 8)), 1)
        for i, eff in enumerate(effs):
            p.stage(key, Payload(
                key=key, type_name=type_name, effect=eff,
                commit_dc=dc, commit_time=int(ct), snapshot_vc=VC(vc),
                txid=("ckpt-seed", 0), certified=True))
            if not p.owns(key):
                # capacity miss mid-seed: the eviction migrated the
                # key (checkpoint seed + suffix replay) to the host
                return False
            if (i + 1) % lanes == 0 and i + 1 < len(effs):
                p.gc(VC(vc))
                if not p.owns(key):
                    return False  # overflow eviction during the fold
        stats.registry.ckpt_seed_device_keys.inc()
        return True

    def stage(self, key, type_name: str, payload: Payload,
              stable_vc: Optional[VC]):
        """Route one committed effect to its type plane.  Returns the
        BOUNCE effect (or None) when the key was evicted DURING the
        decode (unlogged mode only): the bounced part never landed on
        the device and the eviction's exported state predates it, so
        the caller must land it on the host path itself — with a log
        it would be replayed from there (PartitionManager._publish).
        For maps the bounce is the residual entry subset the export
        could not cover (MapPlane.stage_residual); for flat planes it
        is the whole effect."""
        p = self.planes[type_name]
        if not p._warm_kicked:
            p.kick_warm()
        if p._schedule is not self.flush_scheduler:
            p._schedule = self.flush_scheduler
        # the txid-correlated device-plane hop of the txn span tree
        # (instant: the XLA work happens later, at flush time) plus the
        # flight-recorder record of the _publish window the round-5
        # set_aw bug lives in
        tracer.instant("device_stage", "device", txid=payload.txid,
                       key=key, type=type_name)
        # per-op stage events get their OWN subsystem ring: at serving
        # rates they would otherwise evict the rare flush/evict/gc
        # events that bound the suspect _publish window from the shared
        # 512-deep "device" ring within a second
        recorder.record("device_stage", "stage", plane=type_name,
                        key=key, txid=payload.txid,
                        commit_time=payload.commit_time)
        p._note_staged_vc(payload)
        p.stage(key, payload)
        evicted_mid_decode = not p.owns(key)
        p.maybe_flush_gc(stable_vc)
        if not (self._evict_export and evicted_mid_decode):
            return None
        if isinstance(p, MapPlane):
            return p.stage_residual
        return payload.effect

    def read(self, key, type_name: str, read_vc: Optional[VC],
             txid=None):
        """:meth:`read_many` with one key; ReadBelowBase where the
        device does not hold it (_PlaneBase.read)."""
        got = self.read_many([key], type_name, read_vc, txid=txid)
        if key not in got:
            raise ReadBelowBase()
        return got[key]

    def read_many(self, keys: list, type_name: str,
                  read_vc: Optional[VC], txid=None) -> dict:
        """{key: state} for device-owned keys; callers take the host
        path for the rest."""
        # txid-tagged so the span joins its txn's tree and obeys
        # per-txid sampling; untagged reads fall back to sampled()'s
        # 1-in-N thinning instead of flooding the ring
        with tracer.span("device_read_many", "device", txid=txid,
                         n=len(keys), type=type_name):
            t0 = time.perf_counter()
            out = self.planes[type_name].read_many(keys, read_vc)
        stats.registry.device_read_latency.observe(
            time.perf_counter() - t0)
        return out

    def gc(self, stable_vc: VC) -> None:
        with tracer.span("device_gc_all", "device"):
            for p in self.planes.values():
                p.gc(stable_vc)
        self.refresh_shard_stats()

    def flush(self) -> None:
        with tracer.span("device_flush_all", "device"):
            for p in self.planes.values():
                p.flush()

    def pending(self) -> int:
        return sum(len(p.rows) for p in self.planes.values())
