"""Device-resident stable clock plane — the GST as a mesh collective.

Under ring placement (Config.device_placement="ring") partition p's
data plane lives on chip p % n_devices.  This module puts the stable
METADATA there too: each partition's stable VC row (the quantity the
reference gossips once a second, src/meta_data_sender.erl:224-255) is
mirrored onto the partition's own chip, and the DC's stable snapshot —
the column-wise min over partitions (src/stable_time_functions.erl:
39-85) — is ONE sharded XLA program whose min-reduce crosses the
devices over ICI (an all-gather of the per-chip rows; the
ShardedOrsetStore.gc_collective pattern, antidote_tpu/mat/sharded.py;
SURVEY §7.7).

The host fold (StableTimeTracker, meta/gossip.py) stays fully wired as
the ORACLE: every row mirrored to the device is also folded on host,
and tests assert the two snapshots are identical.  In a multi-node DC
this plane replaces the LOCAL (per-node) fold; the cross-node level
remains gossip (cluster/node.py ClusterStablePlane) — on a multi-host
TPU pod the mesh spans the hosts and this same program spans the DC.

Row layout: device-major blocks.  Device k holds the rows of the
partitions ring-placed on it ({p : p % n == k}), padded to a common
row count with +inf rows (min-neutral).  A row update touches only its
device's small block; the fold builds one global array from the
per-device blocks (no host gather) and runs the collective.
"""

from __future__ import annotations

import threading
from typing import List, Optional

import numpy as np

from antidote_tpu.clocks import VC
from antidote_tpu.meta.gossip import StableTimeTracker

_I64_MAX = np.iinfo(np.int64).max

from antidote_tpu.runtime import COLLECTIVE_LOCK as _COLLECTIVE_LOCK


def _pow2(n: int, floor: int = 8) -> int:
    return max(floor, 1 << (max(n, 1) - 1).bit_length())


class DeviceStableTimeTracker(StableTimeTracker):
    """StableTimeTracker whose published fold runs on the device mesh.

    ``put`` updates the host row (the oracle path, unchanged) and marks
    the partition's device row dirty; ``get_stable_snapshot`` flushes
    dirty rows to their chips and serves the min from the collective.
    ``oracle_snapshot`` serves the host fold for equality checks."""

    def __init__(self, dc_id, n_partitions: int, devices: List,
                 placement: Optional[List[int]] = None,
                 domain=None, sender=None):
        super().__init__(dc_id, n_partitions, domain=domain,
                         sender=sender)
        if not devices:
            raise ValueError("device plane needs at least one device")
        self.devices = list(devices)
        n = len(self.devices)
        #: row -> device index.  Default mirrors the data-plane ring
        #: (txn/node.py places partition p's plane on devices[p % n]);
        #: a cluster member passes its local slice's GLOBAL ring slots
        #: so each row still sits beside its partition's plane.
        if placement is None:
            placement = [p % n for p in range(n_partitions)]
        if len(placement) != n_partitions or any(
                not 0 <= k < n for k in placement):
            raise ValueError("placement must map every row to a device")
        self.placement = list(placement)
        #: row -> (device index, slot within that device's block)
        self._slots = {}
        per_dev = [0] * n
        for p, k in enumerate(self.placement):
            self._slots[p] = (k, per_dev[k])
            per_dev[k] += 1
        self._rpd = max(1, max(per_dev, default=0))
        self._dev_lock = threading.Lock()
        #: serializes device folds + the monotone publish.  The fold
        #: itself (device transfers + the collective + the D2H fetch)
        #: runs under THIS mutex only — holding self._lock/_dev_lock
        #: across it stalled every delivery/heartbeat put() for the
        #: fold duration (round-5 advisor finding); those locks now
        #: cover just the host-side row copy.
        self._fold_lock = threading.Lock()
        self._d_pad = _pow2(self.domain.d)
        #: host mirror of the device rows, device-major (+inf pads are
        #: min-neutral)
        self._blocks_host = [
            np.full((self._rpd, self._d_pad), _I64_MAX, np.int64)
            for _ in range(n)
        ]
        self._blocks_dev = [None] * n  # lazily device_put per block
        self._dirty = set(range(n_partitions))
        self._published_dev: Optional[VC] = None
        self._fold_fn = None
        self._mesh = None

    # -- row ingestion ----------------------------------------------------

    def put(self, partition: int, vc: VC) -> None:
        # one critical section for the host-row update AND the
        # dirty-mark (the tracker lock is an RLock for exactly this):
        # released in between, a snapshot holding both locks could fold
        # the NEW row on host but skip flushing the device mirror
        # (partition not yet dirty) — dev lagging host by one put
        with self._lock:
            super().put(partition, vc)  # the host oracle row
            with self._dev_lock:
                self._dirty.add(partition)

    # -- device plumbing --------------------------------------------------

    def _slot(self, p: int):
        return self._slots[p]

    def _ensure_width(self) -> None:
        """Domain growth (host side pads rows in put) must widen the
        device blocks too; a width change invalidates every block and
        the compiled fold."""
        want = _pow2(self.domain.d)
        if want == self._d_pad:
            return
        self._d_pad = want
        n = len(self.devices)
        self._blocks_host = [
            np.full((self._rpd, self._d_pad), _I64_MAX, np.int64)
            for _ in range(n)
        ]
        self._blocks_dev = [None] * n
        self._dirty = set(range(self.n_partitions))
        self._fold_fn = None

    def _build_fold(self):
        import jax
        from jax.sharding import Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P

        n = len(self.devices)
        self._mesh = Mesh(np.array(self.devices), ("parts",))
        sharding = NamedSharding(self._mesh, P("parts", None))

        if n == 1:
            # degenerate mesh: a plain jitted min (no collective axis)
            self._fold_fn = (jax.jit(lambda m: m.min(axis=0)), sharding)
            return

        def local_min(blk):
            import jax.numpy as jnp

            m = jnp.min(blk, axis=0, keepdims=True)  # (1, D) this chip
            # the cross-device column min.  Rows are int64 µs clocks,
            # and the TPU compiler lowers a 64-bit all-reduce for Sum
            # only (a pmin here is "UNIMPLEMENTED" on a v5e, found by
            # chip_smoke.py's ring leg) — so every chip's row crosses
            # ICI as two 32-bit words and the min is taken locally
            hi = jax.lax.all_gather((m >> 32).astype(jnp.int32), "parts")
            lo = jax.lax.all_gather(
                (m & 0xFFFFFFFF).astype(jnp.uint32), "parts")
            rows = (hi.astype(jnp.int64) << 32) | lo.astype(jnp.int64)
            return jnp.min(rows, axis=0)

        from antidote_tpu.runtime import shard_map_compat

        # check_vma off: every chip gathers the same rows, so the min
        # is replicated, which the checker cannot infer of an all_gather
        fn = jax.jit(shard_map_compat(
            local_min, mesh=self._mesh, in_specs=P("parts", None),
            out_specs=P(None, None), check_vma=False))
        self._fold_fn = (lambda m: fn(m)[0], sharding)

    def _copy_dirty_locked(self):
        """Copy every dirty partition's row into the host-side device
        blocks.  Caller holds self._lock, self._dev_lock AND
        self._fold_lock; this is pure host-array work (the EXACT rows
        the host oracle folds — _grow_if_needed keeps them current),
        so the row locks are held only for the memcpy, not the device
        round trip.  Returns (touched device indices, domain snapshot)
        for the fold that runs after the locks drop."""
        self._ensure_width()
        touched = set()
        for p in self._dirty:
            k, j = self._slot(p)
            row = np.asarray(self.sender.peek_value("stable", p))
            blk = self._blocks_host[k]
            blk[j, :] = _I64_MAX
            blk[j, :len(row)] = row
            touched.add(k)
        self._dirty.clear()
        return touched, self.domain

    # -- snapshots --------------------------------------------------------

    def oracle_snapshot(self) -> VC:
        """The host fold — identical inputs, host min (for tests)."""
        return super().get_stable_snapshot()

    def snapshot_pair(self):
        """(device snapshot, host snapshot) folded from ONE source
        refresh — the oracle-equality form: time-dependent sources
        (min-prepared reads the clock) make two separately-refreshed
        snapshots incomparable.  Both folds read their inputs under
        ONE row-lock hold (a concurrent put() between them would feed
        the later fold newer rows and make the pair transiently
        unequal — observed live with background heartbeats); the
        device round trip itself then runs outside the row locks."""
        if self.sources:
            self.refresh()
        with self._fold_lock:
            with self._lock, self._dev_lock:
                # ONE floor peek shared by both folds: a concurrent
                # seed_floor between two peeks would skew only the
                # later fold
                floor = self.sender.peek("stable_floor")
                touched, domain = self._copy_dirty_locked()
                stable = self.sender.merged("stable")
                host = VC(stable if floor is None
                          else stable.join(floor))
            dev = self._fold_device(touched, domain, floor)
        return dev, host

    def get_stable_snapshot(self) -> VC:
        if self.sources:
            self.refresh()
        if self.n_partitions == 0:
            return super().get_stable_snapshot()
        with self._fold_lock:
            with self._lock, self._dev_lock:
                floor = self.sender.peek("stable_floor")
                touched, domain = self._copy_dirty_locked()
            return self._fold_device(touched, domain, floor)

    def _fold_device(self, touched, domain, floor) -> VC:
        """The device fold: flush touched blocks, run the collective,
        publish monotonically.  Runs under self._fold_lock ONLY (plus
        COLLECTIVE_LOCK around the launch) — delivery/heartbeat put()
        calls proceed concurrently instead of stalling for the whole
        device round trip (round-5 advisor finding); they mark rows
        dirty for the NEXT fold, which the monotone publish orders.
        ``domain`` is the width snapshot taken with the rows — a
        concurrent grow must not skew the dense decode."""
        import jax

        if self._fold_fn is None:
            self._build_fold()
        fold, sharding = self._fold_fn
        n = len(self.devices)
        for k in range(n):
            if k in touched or self._blocks_dev[k] is None:
                self._blocks_dev[k] = jax.device_put(
                    self._blocks_host[k], self.devices[k])
        with _COLLECTIVE_LOCK:
            global_mat = jax.make_array_from_single_device_arrays(
                (n * self._rpd, self._d_pad), sharding,
                self._blocks_dev)
            row = np.asarray(fold(global_mat))
        # +inf pad rows survive the min only when a column is
        # beyond every real row's width — those columns are absent
        # from the domain anyway; mask for safety
        row = np.where(row == _I64_MAX, 0, row)
        gst = domain.from_dense(row[:domain.d])
        if floor is not None:
            gst = gst.join(floor)
        # monotone publish, the device path's own lineage (serialized
        # by self._fold_lock)
        self._published_dev = (
            gst if self._published_dev is None
            else self._published_dev.join(gst))
        return VC(self._published_dev)


def make_stable_tracker(config, dc_id, n_partitions: int,
                        placement: Optional[List[int]] = None,
                        **kw) -> StableTimeTracker:
    """Tracker factory honoring the node's placement policy: the
    device-collective plane when the data plane is ring-placed over a
    real multi-device mesh, the host fold otherwise.  ``placement``
    maps row index -> device index for callers whose rows are a slice
    of a larger ring (cluster members); default is the full ring
    (txn/node.py places partition p's plane on devices()[p % n])."""
    if (config is not None and config.device_store
            and config.device_placement == "ring"):
        import jax

        devs = jax.devices()
        if len(devs) > 1:
            return DeviceStableTimeTracker(dc_id, n_partitions, devs,
                                           placement=placement, **kw)
    return StableTimeTracker(dc_id, n_partitions, **kw)
