"""Coalesced ingest plane for the materializer stores (ISSUE 4).

BENCH_r05 on the live chip put config-3 mvreg at 0.7x its bracket and
the config-4 RGA steady path under water — both per-op scatter-bound:
every plane flush uploaded ~10 separate per-column host arrays (one
``jnp.asarray`` each) and the benches' legacy form dispatched one
append per op.  The PR-3 gate ring already proved the cure on the
dependency gate: persistent device state, ONE small H2D per batch,
scalar-fetch completion.  This module generalizes that staging economy
to the shard stores:

- **One packed H2D per flush.**  Arriving ops coalesce host-side into
  a single ``int64[B, 2+F]`` tensor whose payload section is laid out
  EXACTLY like the store's packed ops rows (``[key_idx, lane_off,
  <ops-row columns>]``), so :func:`packed_append` splits the two index
  columns on device and lands the batch with the store's own
  single-scatter epilogue (``store._scatter_rows``) — no per-column
  uploads, no on-device column shuffle.
- **A coalescing window + row budget.**  ``Config.mat_coalesce_us``
  holds staged rows open so a burst flushes as one dispatch even below
  the ``device_flush_ops`` threshold's worth of rows;
  ``Config.mat_coalesce_rows`` is the hard staging cap past which the
  committer flushes inline (backpressure, like the gate ring's 4x
  rule).  GC/fold cadence stays on its own knobs (``device_gc_ops``,
  the benches' ``gc_every``) — append cadence and fold cadence are
  deliberately decoupled, the reference's amortized ``?OPS_THRESHOLD``
  recipe.
- **Timing.**  :func:`packed_append` is ``@kernel_span``
  (antidote_tpu/obs/prof.py): the host's dispatch time is recorded per
  call and says so; the scatter's device time is a profiler capture's
  (the device plane, by program name).  The flush that calls it is the
  ``device_flush:<type>`` span, its dispatch and its fetch of the
  overflow mask the ``device_dispatch`` / ``device_fetch`` spans
  beneath (mat/device_plane.py).

``ingest_from_config`` is the ONE factory every assembly must route
through (DevicePlane and mat/sharded.py both take its settings), so a
knob like ``mat_ingest=False`` — the legacy per-column baseline the
benches compare against — cannot silently apply to some planes and
not others (the gate_from_config lesson, interdc/dep.py).

INGEST_* metric families (stats.py) record the economy: flushes by
trigger kind, coalesced ops, H2D bytes, and the ops-per-dispatch
amortization gauge the benches gate on directionally
(tools/bench_gate.py: ops/dispatch up, B/op down).

**Wire-to-scatter (ISSUE 6).**  The batched shipping plane delivers a
whole inter-DC batch frame's txns as ONE dependency-gate arrival
(interdc/sub_buf.py ``process_batch`` -> dep.py ``enqueue_batch``),
so the gate admits them in one wave and their decoded ops stage into
this plane back-to-back — inside one ``mat_coalesce_us`` window by
construction.  A wire frame of N txns therefore lands as a handful of
packed flushes (often one), not N per-txn staging rounds: the wire's
frame economy and this plane's dispatch economy compose end-to-end.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from antidote_tpu import stats
from antidote_tpu.mat import store
from antidote_tpu.obs.prof import kernel_span
from antidote_tpu.obs.spans import tracer

#: flush trigger kinds (the ``kind`` label of
#: antidote_ingest_flushes_total): ``rows`` = the device_flush_ops
#: threshold, ``window`` = the coalescing window expired, ``budget`` =
#: the hard row cap forced an inline flush, ``read`` = a reader needed
#: pending rows, ``gc`` = a fold horizon flushed first, ``grow`` = a
#: capacity regrade drained stale-width rows, ``explicit`` = an
#: operator/test flush
INGEST_FLUSH_KINDS = ("rows", "window", "budget", "read", "gc", "grow",
                      "explicit")

_MIN_BUCKET = 64


def bucket(n: int) -> int:
    """Dispatch bucket (powers of FOUR, like the device plane's):
    coarse quantization keeps the XLA program count small at the cost
    of <=4x padding on the rare odd-sized batch."""
    b = _MIN_BUCKET
    while b < n:
        b *= 4
    return b


@dataclass(frozen=True)
class IngestSettings:
    """The ingest plane's knobs — built from Config by
    :func:`ingest_from_config` (the single factory) so every assembly
    honors the same values."""

    #: packed single-upload flushes; False = the legacy per-column
    #: append path (kept as the benches' comparison baseline)
    enabled: bool = True
    #: staging window, µs: rows younger than this may wait for more
    #: arrivals; 0 disables the window (threshold-only flushing)
    coalesce_us: int = 2000
    #: hard staged-row cap per plane: past it the committer flushes
    #: INLINE (backpressure so a lagging flusher cannot let staged
    #: rows grow unboundedly)
    row_budget: int = 8192


def ingest_from_config(config) -> IngestSettings:
    """The one construction path for ingest settings — DevicePlane and
    the sharded stores both call this, so the single-shard and mesh
    assemblies cannot silently honor different knobs."""
    if config is None:
        return IngestSettings()
    return IngestSettings(
        enabled=config.mat_ingest,
        coalesce_us=config.mat_coalesce_us,
        row_budget=config.mat_coalesce_rows)


# ---------------------------------------------------------------------------
# packed layout
#
# The payload section of a packed tensor IS the store's ops-row layout,
# so the device side never shuffles columns.  The plane's decoded rows
# arrive in ``_row_cols`` (append-argument) order; ``PACKED_PERMS``
# maps that order onto the ops layout per store append.  Keyed by
# __name__: the store appends are kernel_span-wrapped but keep their
# names (functools.wraps), and names are stable across the wrapping.

PACKED_PERMS = {
    # ops: [elem, is_add, dot_dc, dot_seq, op_dc, op_ct, obs(D), ss(D)]
    # cols: (slot, is_add, dot_dc, dot_seq, obs_vv, op_dc, op_ct, op_ss)
    "orset_append": (0, 1, 2, 3, 5, 6, 4, 7),
    # ops: [elem, kind, dot_dc, dot_seq, op_dc, op_ct, obs_add(D),
    #       obs_rmv(D), ss(D)]
    # cols: (slot, kind, dot_dc, dot_seq, obs_add, obs_rmv, op_dc,
    #        op_ct, op_ss)
    "rwset_append": (0, 1, 2, 3, 6, 7, 4, 5, 8),
    # ops: [delta, op_dc, op_ct, ss(D)] == cols order
    "counter_append": (0, 1, 2, 3),
    # ops: [ts, tie, val, op_dc, op_ct, ss(D)] == cols order
    "lww_append": (0, 1, 2, 3, 4, 5),
    # ops: [elem, op_dc, op_ct, ss(D)] == cols order
    "setgo_append": (0, 1, 2, 3),
}


def perm_for(append_fn) -> Optional[Tuple[int, ...]]:
    """The ops-layout permutation for a store append, or None when the
    plane has no packed form (RGA documents go through
    rga_store.rga_append_coalesced instead)."""
    return PACKED_PERMS.get(getattr(append_fn, "__name__", ""))


def packed_width(row_cols: Tuple[str, ...], d: int) -> int:
    """Ops-row column count for a plane's row tags ("s" scalar / "vv"
    dense [d] clock)."""
    return sum(d if tag == "vv" else 1 for tag in row_cols)


def pack_rows(rows, capacity: int, d: int, row_cols: Tuple[str, ...],
              perm: Tuple[int, ...]) -> np.ndarray:
    """Coalesce decoded plane rows into ONE packed host tensor
    ``int64[B, 2+F]`` (B = dispatch bucket): column 0 = key index
    (padding rows carry the ``capacity`` drop sentinel, exactly like
    the legacy packer), column 1 = lane offset, then the ops-row
    payload in store layout.  This is the single H2D of a flush."""
    n = len(rows)
    B = bucket(n)
    F = packed_width(row_cols, d)
    out = np.zeros((B, 2 + F), dtype=np.int64)
    out[:, 0] = capacity  # padding keys route to the drop slot
    # column offsets of each row field (in _row_cols index space)
    offs = [0] * len(row_cols)
    off = 2
    for pos in perm:
        offs[pos] = off
        off += d if row_cols[pos] == "vv" else 1
    for i, row in enumerate(rows):
        out[i, 0] = row[0]
        for j, (tag, v) in enumerate(zip(row_cols, row[1:])):
            o = offs[j]
            if tag == "vv":
                for col, s in v:
                    if s > out[i, o + col]:
                        out[i, o + col] = s
            else:
                out[i, o] = v
    out[:n, 1] = store.batch_lane_offsets(out[:n, 0])
    return out


def split_packed(packed: jax.Array, ops_dtype):
    """Device-side split of a packed tensor into the scatter epilogue's
    arguments — shared by :func:`packed_append` and the sharded
    stores' shard_map bodies (mat/sharded.py append_packed)."""
    key_idx = packed[:, 0].astype(jnp.int32)
    lane_off = packed[:, 1].astype(jnp.int32)
    rows = packed[:, 2:].astype(ops_dtype)
    return key_idx, lane_off, rows


@kernel_span("mat.ingest")
@partial(jax.jit, donate_argnums=(0,))
def packed_append(st, packed: jax.Array,
                  active: jax.Array | None = None):
    """Apply one coalesced flush: split the packed tensor's key/lane
    columns and land every row with the store's single donated
    scatter.  Generic over every packed-ring shard state (the payload
    section is already in that state's ops layout); returns
    (state, overflow[B]) with the stores' usual contract (padding and
    masked-off rows never overflow).

    DONATES ``st``'s buffers like the per-column appends it replaces —
    callers must treat the argument as consumed."""
    key_idx, lane_off, rows = split_packed(packed, st.ops.dtype)
    return store._scatter_rows(st, key_idx, lane_off, rows, active)


# ---------------------------------------------------------------------------
# metrics

def note_flush(kind: str) -> None:
    """Count one flush event by trigger kind.  The instant also lands
    on the trace timeline (ISSUE 7): a sampled txn's journey shows the
    packed flush that made its staged ops device-visible right after
    its ``depgate_admit`` span — untagged, so partial sample rates
    thin it instead of flooding the ring."""
    stats.registry.ingest_flushes.inc(kind=kind)
    tracer.instant("ingest_flush", "device", kind=kind)


def note_dispatch(ops: int, h2d_bytes: int, replicas: int = 1) -> None:
    """Record one packed device dispatch (``ops`` coalesced rows in
    one ``h2d_bytes`` upload) and refresh the amortization gauge —
    coalesced ops per dispatch over the process lifetime, the panel
    and bench row the ISSUE's acceptance gates on.  ``replicas``: how
    many chips the upload lands on (the sharded stores replicate the
    packed batch over the mesh, mat/sharded.py) — the byte counter
    reports the REAL H2D traffic, not the logical tensor size."""
    reg = stats.registry
    reg.ingest_dispatches.inc()
    reg.ingest_coalesced_ops.inc(ops)
    reg.ingest_h2d_bytes.inc(h2d_bytes * max(int(replicas), 1))
    total = reg.ingest_dispatches.value()
    if total:
        reg.ingest_ops_per_dispatch.set(
            reg.ingest_coalesced_ops.value() / total)
