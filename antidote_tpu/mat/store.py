"""Device materializer store — the TPU-resident versioned key store.

The reference keeps, per partition, an ETS op ring + a cache of
materialized snapshots per key, GC'd by thresholds (reference
src/materializer_vnode.erl:36-47, 511-647; ring layout doc
include/antidote.hrl:81-90).  The TPU redesign collapses that to:

- a dense **op ring** of L lanes per key (padded, free-slot bitmap), and
- a single **base snapshot per key anchored at the GST**: because the
  batched kernels can materialize at *any* read VC >= base in one call,
  one base snapshot replaces the reference's per-key snapshot list.
  Reads below the GST fall back to log replay, exactly like the
  reference's snapshot-cache miss (src/materializer_vnode.erl:415-419).

TPU-shaped storage decisions (each measured on v5e, 1M keys x 8 lanes):
- Every per-op field lives in ONE row-major ``ops[K*L, F]`` tensor
  (row = one ring slot): an append is a single flat row scatter
  (~13 ms for a 64k-op batch).  Per-field tensors cost a scatter per
  field (~108 ms total) and [K, L, ...]-shaped scatter targets are ~8x
  slower than flat row indices (XLA lowers multi-dim scatters badly).
- Readers get [K, L(, D)] *views* from per-column slices (the
  properties); the reshape fuses into the consuming fold.  A
  materialized [K*L, F] <-> [K, L, F] relayout costs ~19-30 ms — never
  round-trip the layouts.
- GC does NOT compact lanes.  Folded lanes are simply marked free
  (``valid &= ~stable`` — elementwise, fused) and appends place ops in
  free lanes by rank (a [B, L] cumsum over gathered bitmap rows).
  Lane order carries no meaning: materialization is an associative,
  commutative lattice fold (mat/kernels.py), so fragmentation is free.
  The reference compacts because its ring is a sequential Erlang tuple
  walked oldest-first (include/antidote.hrl:81-90); a batched fold has
  no such need — compaction cost 1.6 s/step in scatter form.
- GC is amortized: callers fold every G steps (the reference GCs per
  key every ``?OPS_THRESHOLD`` = 50 ops, src/materializer_vnode.erl:46
  — also amortized), sizing L to cover G batches of expected per-key
  arrivals.

Shapes: K keys, L ring lanes, E element slots, D dc columns.  Appends
whose key ring is full are reported back (overflow) so the control plane
can trigger a GC or spill to the log; reads of overflowed keys stay
correct via log replay.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass, replace
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from antidote_tpu.clocks import dense
from antidote_tpu.mat import kernels
from antidote_tpu.obs.prof import kernel_span

log = logging.getLogger(__name__)

# packed op-tensor columns (OR-Set): scalars, then obs VV, then op SS
_ELEM, _ISADD, _DOTDC, _DOTSEQ, _OPDC, _OPCT, _NSCAL = 0, 1, 2, 3, 4, 5, 6


def _gather_key_rows(st, key_idx: jax.Array, read_vc: jax.Array,
                     dc_col: int, ct_col: int, ss_off: int):
    """Shared transaction-read gather: the B requested keys' ring rows
    plus their Clock-SI inclusion mask at ``read_vc``.  Returns
    (ops[B, L, F], mask[B, L]).  Every per-type ``*_read_keys`` is this
    gather + that type's fold over its own columns."""
    L = st.n_lanes
    d = st._d
    flat = key_idx[:, None] * L + jnp.arange(L, dtype=key_idx.dtype)
    ops = st.ops[flat]                                   # [B, L, F]
    valid = st.valid[flat]                               # [B, L]
    B = key_idx.shape[0]
    base_vc = jnp.broadcast_to(st.base_vc, (B, d))
    has_base = jnp.broadcast_to(st.has_base, (B,))
    mask = kernels.inclusion_mask(
        ops[..., dc_col], ops[..., ct_col], ops[..., ss_off:ss_off + d],
        valid, base_vc, has_base, read_vc)
    return ops, mask


def _free_lanes(valid2d: jax.Array, key_idx: jax.Array,
                lane_off: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Lane for each batch op = its (lane_off+1)-th free slot; lane == L
    signals overflow.  ``valid2d``: bool[K, L]; key_idx/lane_off: int[B]."""
    L = valid2d.shape[1]
    rows = valid2d[key_idx]                            # [B, L] gather
    free = ~rows
    rank = jnp.cumsum(free, axis=1) - 1                # rank among free
    hot = free & (rank == lane_off[:, None])
    lane = jnp.where(jnp.any(hot, axis=1), jnp.argmax(hot, axis=1), L)
    return lane.astype(jnp.int32), lane >= L


def _scatter_rows(st, key_idx: jax.Array, lane_off: jax.Array,
                  rows: jax.Array, active: jax.Array | None = None):
    """Shared append epilogue: place each packed row in its key's next
    free ring lane and mark it live.  ``active`` (bool[B], optional)
    drops masked-off ops entirely — no scatter, no overflow — the
    sharded stores' this-chip's-keys filter.  Returns (state,
    overflow[B]); overflowed ops are NOT stored."""
    L = st.n_lanes
    lane, overflow = _free_lanes(st.valid2d, key_idx, lane_off)
    if active is not None:
        overflow = overflow & active
    drop = (lane >= L) if active is None else ((lane >= L) | ~active)
    flat = jnp.where(drop, st.ops.shape[0], key_idx * L + lane)
    ops = st.ops.at[flat].set(rows, mode="drop")
    valid = st.valid.at[flat].set(True, mode="drop")
    return replace(st, ops=ops, valid=valid), overflow


@dataclass
class OrsetShardState:
    """Device arrays for one OR-Set shard (a pytree).

    ``ops[K*L, 6+2D]`` packs per-op fields column-wise:
    [elem_slot, is_add, dot_dc, dot_seq, op_dc, op_ct,
     obs_vv(D), op_ss(D)]; [K, L]-shaped views come from the
    properties.  ``n_lanes`` is static metadata."""

    dots: jax.Array      # int[K, E, D] base snapshot (live dot table)
    base_vc: jax.Array   # int[D] snapshot time of the base (shard GST)
    has_base: jax.Array  # bool[] whether base_vc is meaningful
    ops: jax.Array       # int[K*L, 6+2D] packed op ring (flat rows)
    valid: jax.Array     # bool[K*L] lane occupancy
    n_lanes: int

    @property
    def _d(self) -> int:
        return (self.ops.shape[-1] - _NSCAL) // 2

    def _col(self, c) -> jax.Array:
        return self.ops[:, c].reshape(-1, self.n_lanes)

    @property
    def valid2d(self) -> jax.Array:
        return self.valid.reshape(-1, self.n_lanes)

    @property
    def count(self) -> jax.Array:
        """int32[K]: live ops per key (derived from the bitmap)."""
        return jnp.sum(self.valid2d, axis=1, dtype=jnp.int32)

    @property
    def elem_slot(self):
        return self._col(_ELEM)

    @property
    def is_add(self):
        return self._col(_ISADD) != 0

    @property
    def dot_dc(self):
        return self._col(_DOTDC)

    @property
    def dot_seq(self):
        return self._col(_DOTSEQ)

    @property
    def op_dc(self):
        return self._col(_OPDC)

    @property
    def op_ct(self):
        return self._col(_OPCT)

    @property
    def obs_vv(self):
        d = self._d
        return self.ops[:, _NSCAL:_NSCAL + d].reshape(
            -1, self.n_lanes, d)

    @property
    def op_ss(self):
        d = self._d
        return self.ops[:, _NSCAL + d:].reshape(-1, self.n_lanes, d)


jax.tree_util.register_dataclass(
    OrsetShardState,
    data_fields=["dots", "base_vc", "has_base", "ops", "valid"],
    meta_fields=["n_lanes"],
)


def orset_shard_init(n_keys: int, n_lanes: int, n_slots: int, n_dcs: int,
                     dtype=jnp.int64) -> OrsetShardState:
    K, L, E, D = n_keys, n_lanes, n_slots, n_dcs
    ops = jnp.zeros((K * L, _NSCAL + 2 * D), dtype=dtype)
    ops = ops.at[:, _ELEM].set(E)  # empty lanes route to the drop slot
    return OrsetShardState(
        dots=jnp.zeros((K, E, D), dtype=dtype),
        base_vc=jnp.zeros((D,), dtype=dtype),
        has_base=jnp.zeros((), dtype=bool),
        ops=ops,
        valid=jnp.zeros((K * L,), dtype=bool),
        n_lanes=L,
    )


@kernel_span("mat.store")
@partial(jax.jit, donate_argnums=(0,))
def orset_append(
    st: OrsetShardState,
    key_idx: jax.Array,   # int32[B]
    lane_off: jax.Array,  # int32[B] occurrence index of the key in batch
    elem_slot: jax.Array, is_add: jax.Array,
    dot_dc: jax.Array, dot_seq: jax.Array, obs_vv: jax.Array,
    op_dc: jax.Array, op_ct: jax.Array, op_ss: jax.Array,
    active: jax.Array | None = None,
) -> Tuple[OrsetShardState, jax.Array]:
    """Scatter a batch of B committed ops into free ring lanes.  Returns
    (state, overflow[B]); overflowed ops are NOT stored — the caller
    must GC and retry or serve those keys from the log.

    ``active`` (bool[B], optional) drops masked-off ops entirely (no
    scatter, no overflow) — the sharded store's this-chip's-keys filter
    (antidote_tpu/mat/sharded.py)."""
    dt = st.ops.dtype
    col = lambda a: a.astype(dt)[:, None]
    rows = jnp.concatenate([
        col(elem_slot), col(is_add), col(dot_dc), col(dot_seq),
        col(op_dc), col(op_ct), obs_vv.astype(dt), op_ss.astype(dt),
    ], axis=1)                                          # [B, 6+2D]
    return _scatter_rows(st, key_idx, lane_off, rows, active)


def _orset_gc_impl(st: OrsetShardState, gst: jax.Array) -> OrsetShardState:
    cvc = dense.commit_vc(st.op_ss, st.op_dc, st.op_ct)      # [K, L, D]
    stable = st.valid2d & dense.le(cvc, gst[None, None, :])
    dots = kernels.orset_apply(
        st.dots, st.elem_slot, st.is_add, st.dot_dc, st.dot_seq,
        st.obs_vv, stable,
    )
    return replace(
        st,
        dots=dots,
        base_vc=jnp.maximum(st.base_vc, gst.astype(st.base_vc.dtype)),
        has_base=jnp.ones((), dtype=bool),
        valid=st.valid & ~stable.reshape(-1),
    )


#: the same fold WITHOUT donation — orset_gc_full's jnp path, so its
#: flag-independent contract ("st stays valid") holds on every path
_orset_gc_nodonate = kernel_span("mat.store", name="orset_gc_nodonate")(
    jax.jit(_orset_gc_impl))


@kernel_span("mat.store")
@partial(jax.jit, donate_argnums=(0,))
def orset_gc(st: OrsetShardState, gst: jax.Array) -> OrsetShardState:
    """Fold every ring op with commit VC <= GST into the base snapshot
    and free its lane (the batched op_insert_gc/snapshot_insert_gc,
    reference src/materializer_vnode.erl:511-647).

    Safe because the GST is a *stable* time: no op with commit VC <= GST
    can still be in flight (reference dc_utilities:get_stable_snapshot
    contract), so folding is permanent and base_vc := max(base_vc, gst).
    Lanes are freed, not compacted (see module doc).

    DONATES ``st``'s buffers (the live planes' steady-state GC aliases
    the multi-hundred-MB ops tensor in place); callers that must keep
    ``st`` use :func:`orset_gc_full`, whose paths all preserve it."""
    return _orset_gc_impl(st, gst)


@kernel_span("mat.store")
@jax.jit
def orset_read(st: OrsetShardState, read_vc: jax.Array) -> jax.Array:
    """bool[K, E]: element presence for every key at ``read_vc`` in one
    batched materialization (base + included ring ops).

    Requires read_vc >= base_vc (reads under the base fall back to log
    replay at the control plane, as in the reference's cache miss)."""
    K = st.dots.shape[0]
    base_vc = jnp.broadcast_to(st.base_vc, (K, st.base_vc.shape[0]))
    has_base = jnp.broadcast_to(st.has_base, (K,))
    mask = kernels.inclusion_mask(
        st.op_dc, st.op_ct, st.op_ss, st.valid2d, base_vc, has_base,
        read_vc)
    dots = kernels.orset_apply(
        st.dots, st.elem_slot, st.is_add, st.dot_dc, st.dot_seq,
        st.obs_vv, mask)
    return kernels.orset_present(dots)


def _fused_requested(fused, st) -> bool:
    """Resolve a ``fused`` selector against a shard.  "auto" takes the
    Pallas path only where it can be honoured — a TPU backend and int32
    rows (the kernels compute in int32, so µs-int64 live shards would
    truncate their timestamps).  An EXPLICIT request that cannot be
    honoured raises: quietly answering from the jnp path would let a
    caller believe it had measured, or validated, the kernel."""
    if fused == "auto":
        return (jax.default_backend() == "tpu"
                and st.ops.dtype == jnp.int32)
    if fused and st.ops.dtype != jnp.int32:
        raise ValueError(
            f"fused={fused!r} needs an int32 shard (the Pallas kernels "
            f"compute in int32); this one is {st.ops.dtype}")
    return bool(fused)


def orset_read_full(st: OrsetShardState, read_vc: jax.Array,
                    fused: str | bool = "auto",
                    block_k: int | None = None,
                    interpret: bool = False) -> jax.Array:
    """bool[K, E]: full-shard presence read, flag-selecting the Pallas
    fused kernel (antidote_tpu/mat/pallas_kernels.py orset_read_packed —
    one HBM pass over the packed rows, nothing but the presence block
    leaves VMEM) over the jnp reference path (:func:`orset_read`).

    ``fused``: True / False / "auto" / "hybrid" (see
    :func:`_fused_requested`; "hybrid" runs the inclusion mask in XLA
    and only the fold in Pallas).  ``interpret`` runs the kernel in
    Pallas interpret mode — what the CPU tests ask for by name; it is
    never selected here, so off a TPU a fused request without it fails
    in the Pallas lowering instead of being interpreted.
    """
    if not _fused_requested(fused, st):
        return orset_read(st, read_vc)
    from antidote_tpu.mat import pallas_kernels

    K = st.dots.shape[0]
    args = (st.dots, st.ops, st.valid, st.base_vc, st.has_base,
            read_vc.astype(st.ops.dtype))
    if fused == "hybrid":
        fn, ladder = pallas_kernels.orset_read_hybrid, (512, 256, 128)
    else:
        fn, ladder = pallas_kernels.orset_read_packed, (256, 128)
    if block_k is not None:
        return fn(*args, block_k=min(block_k, K), interpret=interpret)
    return _probe_block_k(
        fn, args, (fn.__name__, interpret, st.dots.shape, st.ops.shape),
        K, interpret, ladder)


#: (kernel, interpret, shapes) -> the block_k that compiled there
BLOCK_K_CHOSEN: dict = {}


def _probe_block_k(fn, args, cache_key, K, interpret, ladder):
    """Call ``fn(*args, block_k=..)`` with the largest block size this
    chip's scoped-VMEM budget accepts, probing the descending ladder
    once per ``cache_key`` (budgets differ per TPU generation —
    measured on v5 lite: the hybrid read at block_k=512 requests 26.18M
    against the 16.00M limit).  Pallas/Mosaic raises the VMEM overflow
    synchronously at the dispatching call, so the probe needs no
    execution round-trip; any other error is raised as it is.  The
    block chosen is logged and kept in :data:`BLOCK_K_CHOSEN`."""
    bk = BLOCK_K_CHOSEN.get(cache_key)
    if bk is not None:
        return fn(*args, block_k=min(bk, K), interpret=interpret)
    last = None
    for bk in ladder:
        try:
            out = fn(*args, block_k=min(bk, K), interpret=interpret)
        except Exception as e:  # noqa: BLE001 — inspect + reraise
            if "vmem" not in str(e).lower():
                raise
            last = e
            continue
        BLOCK_K_CHOSEN[cache_key] = bk
        log.info("%s: block_k=%d compiled (K=%d)", fn.__name__, bk, K)
        return out
    raise last


def orset_gc_full(st: OrsetShardState, gst: jax.Array,
                  fused: str | bool = "auto",
                  block_k: int | None = None,
                  interpret: bool = False) -> OrsetShardState:
    """:func:`orset_gc` flag-selecting the fused Pallas fold
    (pallas_kernels.orset_gc_packed — one HBM pass over the packed rows;
    the jnp path's [K, L, D] commit-VC tensor and one-hot select
    intermediates cost ~10x the pass's bandwidth floor, measured 34 ms
    vs a ~4 ms floor per GC at 1M keys on the round-5 bench chip).

    Same ``fused`` / ``interpret`` contract as :func:`orset_read_full`,
    EXCEPT "auto" resolves to the jnp path: measured on the round-5
    bench chip the fused fold is SLOWER (58.8 ms vs 24.5 ms at 1M keys
    — XLA already fuses the GC chain well, and the kernel's unrolled
    one-hot fold is VPU-bound), unlike the read where the Pallas kernel
    wins 2.4x.  Kept for explicit fused=True use on TPU generations
    with more VMEM/VPU headroom; the kernel is equality-tested against
    orset_gc (tests/unit/test_pallas_kernels.py).

    Unlike :func:`orset_gc`, ``st`` is NOT consumed on ANY path: the
    jnp path runs the non-donating jit and the fused path never
    donated — uniform semantics regardless of the flag (the previous
    flag-dependent donation was a use-after-donate hazard: caller code
    touching st afterwards worked under fused=True and crashed — or
    silently read donated buffers — under the default)."""
    if fused == "auto" or not _fused_requested(fused, st):
        return _orset_gc_nodonate(st, gst)
    from antidote_tpu.mat import pallas_kernels

    K = st.dots.shape[0]
    args = (st.dots, st.ops, st.valid, gst.astype(st.ops.dtype))
    fn = pallas_kernels.orset_gc_packed
    if block_k is not None:
        ndots, nvalid = fn(*args, block_k=min(block_k, K),
                           interpret=interpret)
    else:
        ndots, nvalid = _probe_block_k(
            fn, args,
            (fn.__name__, interpret, st.dots.shape, st.ops.shape),
            K, interpret, (512, 256, 128))
    return replace(
        st,
        dots=ndots.astype(st.dots.dtype),
        base_vc=jnp.maximum(st.base_vc, gst.astype(st.base_vc.dtype)),
        has_base=jnp.ones((), dtype=bool),
        valid=nvalid,
    )


@kernel_span("mat.store")
@jax.jit
def orset_read_keys(st: OrsetShardState, key_idx: jax.Array,
                    read_vc: jax.Array) -> jax.Array:
    """int[B, E, D]: folded live-dot tables for just the requested keys
    at ``read_vc`` — the transaction read path (B small), vs
    :func:`orset_read` which folds the whole shard.

    Gathers the B keys' ring rows ([B, L, F]) and base rows, then runs
    the same inclusion-mask + lattice fold as the full-shard read.
    Requires read_vc >= base_vc (callers fall back to log replay below
    the base, the reference's snapshot-cache miss)."""
    d = st._d
    ops, mask = _gather_key_rows(st, key_idx, read_vc,
                                 _OPDC, _OPCT, _NSCAL + d)
    return kernels.orset_apply(
        st.dots[key_idx], ops[..., _ELEM], ops[..., _ISADD] != 0,
        ops[..., _DOTDC], ops[..., _DOTSEQ], ops[..., _NSCAL:_NSCAL + d],
        mask)


@kernel_span("mat.store")
@partial(jax.jit, donate_argnums=(0,))
def orset_purge_keys(st: OrsetShardState,
                     key_idx: jax.Array) -> OrsetShardState:
    """Free every ring lane and zero the base rows of the given keys —
    used when a key is evicted to the host path (element-slot or lane
    overflow); its history is then served by log replay.  Out-of-range
    indices (padding) are dropped."""
    L = st.n_lanes
    flat = (key_idx[:, None] * L
            + jnp.arange(L, dtype=key_idx.dtype)).reshape(-1)
    return replace(
        st,
        valid=st.valid.at[flat].set(False, mode="drop"),
        dots=st.dots.at[key_idx].set(0, mode="drop"),
    )


def orset_grow(st: OrsetShardState, n_keys: int | None = None,
               n_slots: int | None = None,
               n_dcs: int | None = None) -> OrsetShardState:
    """Host-side capacity regrade: widen keys / element slots / DC
    columns (never shrink).  One host repack + re-upload; rare (called
    when a directory fills), so simplicity over speed."""
    K, E, D = st.dots.shape
    L = st.n_lanes
    nk, ne, nd = (n_keys or K), (n_slots or E), (n_dcs or D)
    if (nk, ne, nd) == (K, E, D):
        return st
    ops = np.asarray(st.ops).reshape(K, L, -1)
    scal = ops[..., :_NSCAL]
    obs = ops[..., _NSCAL:_NSCAL + D]
    ss = ops[..., _NSCAL + D:]
    padD = ((0, 0), (0, 0), (0, nd - D))
    ops = np.concatenate(
        [scal, np.pad(obs, padD), np.pad(ss, padD)], axis=-1)
    if nk > K:
        # invalid-lane sentinel values don't matter (folds mask by
        # `valid`), so zero rows are fine
        ops = np.pad(ops, ((0, nk - K), (0, 0), (0, 0)))
    valid = np.pad(np.asarray(st.valid).reshape(K, L), ((0, nk - K), (0, 0)))
    dots = np.pad(np.asarray(st.dots),
                  ((0, nk - K), (0, ne - E), (0, nd - D)))
    return OrsetShardState(
        dots=jnp.asarray(dots),
        base_vc=jnp.asarray(np.pad(np.asarray(st.base_vc), (0, nd - D))),
        has_base=st.has_base,
        ops=jnp.asarray(ops.reshape(nk * L, -1)),
        valid=jnp.asarray(valid.reshape(-1)),
        n_lanes=L,
    )


# ---------------------------------------------------------------------------
# register_mv shard — the OR-Set ring layout with a cross-slot fold
#
# An MV-register is structurally an OR-Set over *value slots*: an assign
# mints a dot for its value and cancels the dots it observed, concurrent
# assigns keep multiple live slots (reference antidote_crdt_register_mv
# semantics, crdt/registers.py host oracle).  The one difference is the
# cancellation scope: an assign's observed VV kills dots in EVERY slot
# (it observed the whole register), not just its own slot — which is
# exactly kernels.mvreg_apply vs kernels.orset_apply.  The ring layout,
# append, purge, and grow are therefore shared with the OR-Set
# (OrsetShardState; a reset is a row with val_slot=E, dot_seq=0 — it
# contributes only its observed VV).


@kernel_span("mat.store")
@partial(jax.jit, donate_argnums=(0,))
def mvreg_gc(st: OrsetShardState, gst: jax.Array) -> OrsetShardState:
    """Fold stable assigns into the base dot table (same stability
    contract as orset_gc)."""
    cvc = dense.commit_vc(st.op_ss, st.op_dc, st.op_ct)
    stable = st.valid2d & dense.le(cvc, gst[None, None, :])
    dots = kernels.mvreg_apply(
        st.dots, st.elem_slot, st.dot_dc, st.dot_seq, st.obs_vv, stable)
    return replace(
        st,
        dots=dots,
        base_vc=jnp.maximum(st.base_vc, gst.astype(st.base_vc.dtype)),
        has_base=jnp.ones((), dtype=bool),
        valid=st.valid & ~stable.reshape(-1),
    )


@kernel_span("mat.store")
@jax.jit
def mvreg_read(st: OrsetShardState, read_vc: jax.Array) -> jax.Array:
    """int[K, E, D]: live value-slot dot tables at ``read_vc``."""
    K = st.dots.shape[0]
    base_vc = jnp.broadcast_to(st.base_vc, (K, st.base_vc.shape[0]))
    has_base = jnp.broadcast_to(st.has_base, (K,))
    mask = kernels.inclusion_mask(
        st.op_dc, st.op_ct, st.op_ss, st.valid2d, base_vc, has_base,
        read_vc)
    return kernels.mvreg_apply(
        st.dots, st.elem_slot, st.dot_dc, st.dot_seq, st.obs_vv, mask)


@kernel_span("mat.store")
@jax.jit
def mvreg_read_keys(st: OrsetShardState, key_idx: jax.Array,
                    read_vc: jax.Array) -> jax.Array:
    """int[B, E, D]: live dot tables for just the requested keys (the
    transaction read path; see orset_read_keys)."""
    d = st._d
    ops, mask = _gather_key_rows(st, key_idx, read_vc,
                                 _OPDC, _OPCT, _NSCAL + d)
    return kernels.mvreg_apply(
        st.dots[key_idx], ops[..., _ELEM], ops[..., _DOTDC],
        ops[..., _DOTSEQ], ops[..., _NSCAL:_NSCAL + d], mask)


# ---------------------------------------------------------------------------
# register_lww shard — packed ring over (ts, tiebreak, value-id) rows
#
# Last-writer-wins needs no dot algebra: the fold is a lexicographic max
# over (ts, tie) among the base and every included op
# (kernels.lww_read), which is commutative/idempotent, so GC folding and
# ring fragmentation are free exactly as for the OR-Set.  The tiebreak
# is a host-packed int64 (actor rank << seq bits | seq; the device plane
# owns the rank directory and repacks on actor arrival) so the device
# compare matches the host oracle's (ts, (actor, seq)) order
# (crdt/registers.py RegisterLWW.update).

# packed columns (lww): [ts, tie, val, op_dc, op_ct, op_ss(D)]
_LTS, _LTIE, _LVAL, _LOPDC, _LOPCT, _LNSCAL = 0, 1, 2, 3, 4, 5


@dataclass
class LwwShardState:
    """``ops[K*L, 5+D]`` packs [ts, tie, val, op_dc, op_ct, op_ss(D)];
    base value id -1 = unwritten (host maps to the empty register)."""

    base_ts: jax.Array   # int[K]
    base_tie: jax.Array  # int[K]
    base_val: jax.Array  # int[K] interned value ids (-1 = none)
    base_vc: jax.Array   # int[D]
    has_base: jax.Array  # bool[]
    ops: jax.Array       # int[K*L, 5+D]
    valid: jax.Array     # bool[K*L]
    n_lanes: int

    @property
    def _d(self) -> int:
        return self.ops.shape[-1] - _LNSCAL

    def _col(self, c) -> jax.Array:
        return self.ops[:, c].reshape(-1, self.n_lanes)

    @property
    def valid2d(self) -> jax.Array:
        return self.valid.reshape(-1, self.n_lanes)

    @property
    def op_ts(self):
        return self._col(_LTS)

    @property
    def op_tie(self):
        return self._col(_LTIE)

    @property
    def op_val(self):
        return self._col(_LVAL)

    @property
    def op_dc(self):
        return self._col(_LOPDC)

    @property
    def op_ct(self):
        return self._col(_LOPCT)

    @property
    def op_ss(self):
        return self.ops[:, _LNSCAL:].reshape(-1, self.n_lanes, self._d)


jax.tree_util.register_dataclass(
    LwwShardState,
    data_fields=["base_ts", "base_tie", "base_val", "base_vc",
                 "has_base", "ops", "valid"],
    meta_fields=["n_lanes"],
)


def lww_shard_init(n_keys: int, n_lanes: int, n_dcs: int,
                   dtype=jnp.int64) -> LwwShardState:
    K, L, D = n_keys, n_lanes, n_dcs
    return LwwShardState(
        base_ts=jnp.zeros((K,), dtype=dtype),
        base_tie=jnp.zeros((K,), dtype=dtype),
        base_val=jnp.full((K,), -1, dtype=dtype),
        base_vc=jnp.zeros((D,), dtype=dtype),
        has_base=jnp.zeros((), dtype=bool),
        ops=jnp.zeros((K * L, _LNSCAL + D), dtype=dtype),
        valid=jnp.zeros((K * L,), dtype=bool),
        n_lanes=L,
    )


@kernel_span("mat.store")
@partial(jax.jit, donate_argnums=(0,))
def lww_append(st: LwwShardState, key_idx, lane_off, ts, tie, val,
               op_dc, op_ct, op_ss, active: jax.Array | None = None):
    dt = st.ops.dtype
    col = lambda a: a.astype(dt)[:, None]
    rows = jnp.concatenate(
        [col(ts), col(tie), col(val), col(op_dc), col(op_ct),
         op_ss.astype(dt)], axis=1)
    return _scatter_rows(st, key_idx, lane_off, rows, active)


@kernel_span("mat.store")
@partial(jax.jit, donate_argnums=(0,))
def lww_gc(st: LwwShardState, gst: jax.Array) -> LwwShardState:
    cvc = dense.commit_vc(st.op_ss, st.op_dc, st.op_ct)
    stable = st.valid2d & dense.le(cvc, gst[None, None, :])
    bts, btie, bval = kernels.lww_read(
        st.base_ts, st.base_tie, st.base_val,
        st.op_ts, st.op_tie, st.op_val, stable)
    return replace(
        st,
        base_ts=bts, base_tie=btie, base_val=bval,
        base_vc=jnp.maximum(st.base_vc, gst.astype(st.base_vc.dtype)),
        has_base=jnp.ones((), dtype=bool),
        valid=st.valid & ~stable.reshape(-1),
    )


@kernel_span("mat.store")
@jax.jit
def lww_read(st: LwwShardState, read_vc: jax.Array):
    """(ts, tie, val)[K] at ``read_vc``."""
    K = st.base_ts.shape[0]
    base_vc = jnp.broadcast_to(st.base_vc, (K, st.base_vc.shape[0]))
    has_base = jnp.broadcast_to(st.has_base, (K,))
    mask = kernels.inclusion_mask(
        st.op_dc, st.op_ct, st.op_ss, st.valid2d, base_vc, has_base,
        read_vc)
    return kernels.lww_read(
        st.base_ts, st.base_tie, st.base_val,
        st.op_ts, st.op_tie, st.op_val, mask)


@kernel_span("mat.store")
@jax.jit
def lww_read_keys(st: LwwShardState, key_idx: jax.Array,
                  read_vc: jax.Array):
    """(ts, tie, val)[B] for just the requested keys."""
    ops, mask = _gather_key_rows(st, key_idx, read_vc,
                                 _LOPDC, _LOPCT, _LNSCAL)
    return kernels.lww_read(
        st.base_ts[key_idx], st.base_tie[key_idx], st.base_val[key_idx],
        ops[..., _LTS], ops[..., _LTIE], ops[..., _LVAL], mask)


@kernel_span("mat.store")
@partial(jax.jit, donate_argnums=(0,))
def lww_purge_keys(st: LwwShardState, key_idx: jax.Array) -> LwwShardState:
    L = st.n_lanes
    flat = (key_idx[:, None] * L
            + jnp.arange(L, dtype=key_idx.dtype)).reshape(-1)
    return replace(
        st,
        valid=st.valid.at[flat].set(False, mode="drop"),
        base_ts=st.base_ts.at[key_idx].set(0, mode="drop"),
        base_tie=st.base_tie.at[key_idx].set(0, mode="drop"),
        base_val=st.base_val.at[key_idx].set(-1, mode="drop"),
    )


def lww_grow(st: LwwShardState, n_keys: int | None = None,
             n_dcs: int | None = None) -> LwwShardState:
    """Host-side capacity regrade (see orset_grow)."""
    K = st.base_ts.shape[0]
    D = st._d
    L = st.n_lanes
    nk, nd = (n_keys or K), (n_dcs or D)
    if (nk, nd) == (K, D):
        return st
    ops = np.asarray(st.ops).reshape(K, L, -1)
    scal = ops[..., :_LNSCAL]
    ss = ops[..., _LNSCAL:]
    ops = np.concatenate(
        [scal, np.pad(ss, ((0, 0), (0, 0), (0, nd - D)))], axis=-1)
    if nk > K:
        ops = np.pad(ops, ((0, nk - K), (0, 0), (0, 0)))
    valid = np.pad(np.asarray(st.valid).reshape(K, L), ((0, nk - K), (0, 0)))
    pad1 = lambda a, fill: np.pad(np.asarray(a), (0, nk - K),
                                  constant_values=fill)
    return LwwShardState(
        base_ts=jnp.asarray(pad1(st.base_ts, 0)),
        base_tie=jnp.asarray(pad1(st.base_tie, 0)),
        base_val=jnp.asarray(pad1(st.base_val, -1)),
        base_vc=jnp.asarray(np.pad(np.asarray(st.base_vc), (0, nd - D))),
        has_base=st.has_base,
        ops=jnp.asarray(ops.reshape(nk * L, -1)),
        valid=jnp.asarray(valid.reshape(-1)),
        n_lanes=L,
    )


def lww_reval(st: LwwShardState, remap: np.ndarray) -> LwwShardState:
    """Host-side value-id remap after the plane compacts its value
    directory (dead interned values dropped): every stored val column
    maps through ``remap`` (old id -> new id; dead ids map to -1 but are
    only present on invalid lanes).  Rare, host-side."""
    ops = np.array(np.asarray(st.ops))
    valid = np.asarray(st.valid)
    v = ops[:, _LVAL]
    ops[:, _LVAL] = np.where(
        valid, remap[np.clip(v, 0, len(remap) - 1)], v)
    bval = np.asarray(st.base_val)
    live = bval >= 0
    bval = np.where(live, remap[np.clip(bval, 0, len(remap) - 1)], bval)
    return replace(st, ops=jnp.asarray(ops), base_val=jnp.asarray(bval))


def lww_retie(st: LwwShardState, remap: np.ndarray,
              rank_shift: int) -> LwwShardState:
    """Host-side tiebreak repack after the actor-rank directory grows:
    every stored tie (rank << rank_shift | seq) has its rank remapped
    through ``remap`` (old rank -> new rank).  Rare (first sight of a
    new actor), so simplicity over speed."""
    mask = (1 << rank_shift) - 1

    def repack(packed, live):
        packed = np.asarray(packed)
        rank = (packed >> rank_shift).astype(np.int64)
        seq = packed & mask
        rank = np.where(live, remap[np.clip(rank, 0, len(remap) - 1)], rank)
        return (rank << rank_shift) | seq

    K = st.base_ts.shape[0]
    L = st.n_lanes
    base_live = np.asarray(st.base_val) >= 0
    ops = np.array(np.asarray(st.ops))
    ops[:, _LTIE] = repack(ops[:, _LTIE], np.asarray(st.valid))
    return replace(
        st,
        base_tie=jnp.asarray(repack(st.base_tie, base_live)),
        ops=jnp.asarray(ops),
    )


# ---------------------------------------------------------------------------
# set_rw shard — the remove-wins two-plane dot lattice
#
# Two dot tables per key (adds / removes) with cross-cancellation
# (kernels.rwset_apply; host oracle crdt/sets.py SetRW).  Ring, append,
# GC-fold, purge, and grow follow the OR-Set machinery; rows carry TWO
# observed VVs (the add-plane one zeroed on add rows and vice versa) so
# the fold needs no per-row kind test for cancellation.  flag_dw shares
# this store with a single implicit element slot (crdt/flags.py FlagDW).

# packed columns (set_rw): scalars, then obs_add VV, obs_rmv VV, op SS
_RELEM, _RKIND, _RDOTDC, _RDOTSEQ, _ROPDC, _ROPCT, _RNSCAL = \
    0, 1, 2, 3, 4, 5, 6


@dataclass
class RwsetShardState:
    """``ops[K*L, 6+3D]`` packs [elem_slot, kind, dot_dc, dot_seq,
    op_dc, op_ct, obs_add(D), obs_rmv(D), op_ss(D)]."""

    adds: jax.Array      # int[K, E, D] base add-dot table
    rmvs: jax.Array      # int[K, E, D] base remove-dot table
    base_vc: jax.Array   # int[D]
    has_base: jax.Array  # bool[]
    ops: jax.Array       # int[K*L, 6+3D]
    valid: jax.Array     # bool[K*L]
    n_lanes: int

    @property
    def _d(self) -> int:
        return (self.ops.shape[-1] - _RNSCAL) // 3

    def _col(self, c) -> jax.Array:
        return self.ops[:, c].reshape(-1, self.n_lanes)

    @property
    def valid2d(self) -> jax.Array:
        return self.valid.reshape(-1, self.n_lanes)

    @property
    def elem_slot(self):
        return self._col(_RELEM)

    @property
    def kind(self):
        return self._col(_RKIND)

    @property
    def dot_dc(self):
        return self._col(_RDOTDC)

    @property
    def dot_seq(self):
        return self._col(_RDOTSEQ)

    @property
    def op_dc(self):
        return self._col(_ROPDC)

    @property
    def op_ct(self):
        return self._col(_ROPCT)

    @property
    def obs_add(self):
        d = self._d
        return self.ops[:, _RNSCAL:_RNSCAL + d].reshape(
            -1, self.n_lanes, d)

    @property
    def obs_rmv(self):
        d = self._d
        return self.ops[:, _RNSCAL + d:_RNSCAL + 2 * d].reshape(
            -1, self.n_lanes, d)

    @property
    def op_ss(self):
        d = self._d
        return self.ops[:, _RNSCAL + 2 * d:].reshape(-1, self.n_lanes, d)


jax.tree_util.register_dataclass(
    RwsetShardState,
    data_fields=["adds", "rmvs", "base_vc", "has_base", "ops", "valid"],
    meta_fields=["n_lanes"],
)


def rwset_shard_init(n_keys: int, n_lanes: int, n_slots: int, n_dcs: int,
                     dtype=jnp.int64) -> RwsetShardState:
    K, L, E, D = n_keys, n_lanes, n_slots, n_dcs
    ops = jnp.zeros((K * L, _RNSCAL + 3 * D), dtype=dtype)
    ops = ops.at[:, _RELEM].set(E)  # empty lanes route to the drop slot
    return RwsetShardState(
        adds=jnp.zeros((K, E, D), dtype=dtype),
        rmvs=jnp.zeros((K, E, D), dtype=dtype),
        base_vc=jnp.zeros((D,), dtype=dtype),
        has_base=jnp.zeros((), dtype=bool),
        ops=ops,
        valid=jnp.zeros((K * L,), dtype=bool),
        n_lanes=L,
    )


@kernel_span("mat.store")
@partial(jax.jit, donate_argnums=(0,))
def rwset_append(st: RwsetShardState, key_idx, lane_off, elem_slot, kind,
                 dot_dc, dot_seq, obs_add, obs_rmv, op_dc, op_ct, op_ss,
                 active: jax.Array | None = None):
    dt = st.ops.dtype
    col = lambda a: a.astype(dt)[:, None]
    rows = jnp.concatenate([
        col(elem_slot), col(kind), col(dot_dc), col(dot_seq),
        col(op_dc), col(op_ct), obs_add.astype(dt), obs_rmv.astype(dt),
        op_ss.astype(dt),
    ], axis=1)
    return _scatter_rows(st, key_idx, lane_off, rows, active)


@kernel_span("mat.store")
@partial(jax.jit, donate_argnums=(0,))
def rwset_gc(st: RwsetShardState, gst: jax.Array) -> RwsetShardState:
    """Fold stable ops into the base planes (orset_gc stability
    contract; max-collapse is prefix-cancel insensitive on both planes,
    so folding commutes with later cancellation)."""
    cvc = dense.commit_vc(st.op_ss, st.op_dc, st.op_ct)
    stable = st.valid2d & dense.le(cvc, gst[None, None, :])
    adds, rmvs = kernels.rwset_apply(
        st.adds, st.rmvs, st.elem_slot, st.kind, st.dot_dc, st.dot_seq,
        st.obs_add, st.obs_rmv, stable)
    return replace(
        st,
        adds=adds, rmvs=rmvs,
        base_vc=jnp.maximum(st.base_vc, gst.astype(st.base_vc.dtype)),
        has_base=jnp.ones((), dtype=bool),
        valid=st.valid & ~stable.reshape(-1),
    )


@kernel_span("mat.store")
@jax.jit
def rwset_read(st: RwsetShardState, read_vc: jax.Array):
    """(adds, rmvs)[K, E, D]: live dot tables for every key at
    ``read_vc`` (requires read_vc >= base_vc, as orset_read)."""
    K = st.adds.shape[0]
    base_vc = jnp.broadcast_to(st.base_vc, (K, st.base_vc.shape[0]))
    has_base = jnp.broadcast_to(st.has_base, (K,))
    mask = kernels.inclusion_mask(
        st.op_dc, st.op_ct, st.op_ss, st.valid2d, base_vc, has_base,
        read_vc)
    return kernels.rwset_apply(
        st.adds, st.rmvs, st.elem_slot, st.kind, st.dot_dc, st.dot_seq,
        st.obs_add, st.obs_rmv, mask)


@kernel_span("mat.store")
@jax.jit
def rwset_read_keys(st: RwsetShardState, key_idx: jax.Array,
                    read_vc: jax.Array):
    """(adds, rmvs)[B, E, D] for just the requested keys (transaction
    read path; see orset_read_keys)."""
    d = st._d
    ops, mask = _gather_key_rows(st, key_idx, read_vc,
                                 _ROPDC, _ROPCT, _RNSCAL + 2 * d)
    return kernels.rwset_apply(
        st.adds[key_idx], st.rmvs[key_idx], ops[..., _RELEM],
        ops[..., _RKIND], ops[..., _RDOTDC], ops[..., _RDOTSEQ],
        ops[..., _RNSCAL:_RNSCAL + d],
        ops[..., _RNSCAL + d:_RNSCAL + 2 * d], mask)


@kernel_span("mat.store")
@partial(jax.jit, donate_argnums=(0,))
def rwset_purge_keys(st: RwsetShardState,
                     key_idx: jax.Array) -> RwsetShardState:
    L = st.n_lanes
    flat = (key_idx[:, None] * L
            + jnp.arange(L, dtype=key_idx.dtype)).reshape(-1)
    return replace(
        st,
        valid=st.valid.at[flat].set(False, mode="drop"),
        adds=st.adds.at[key_idx].set(0, mode="drop"),
        rmvs=st.rmvs.at[key_idx].set(0, mode="drop"),
    )


def rwset_grow(st: RwsetShardState, n_keys: int | None = None,
               n_slots: int | None = None,
               n_dcs: int | None = None) -> RwsetShardState:
    """Host-side capacity regrade (see orset_grow)."""
    K, E, D = st.adds.shape
    L = st.n_lanes
    nk, ne, nd = (n_keys or K), (n_slots or E), (n_dcs or D)
    if (nk, ne, nd) == (K, E, D):
        return st
    ops = np.asarray(st.ops).reshape(K, L, -1)
    scal = ops[..., :_RNSCAL]
    blocks = [ops[..., _RNSCAL + i * D:_RNSCAL + (i + 1) * D]
              for i in range(3)]
    padD = ((0, 0), (0, 0), (0, nd - D))
    ops = np.concatenate(
        [scal] + [np.pad(b, padD) for b in blocks], axis=-1)
    if nk > K:
        ops = np.pad(ops, ((0, nk - K), (0, 0), (0, 0)))
    valid = np.pad(np.asarray(st.valid).reshape(K, L),
                   ((0, nk - K), (0, 0)))
    pad3 = ((0, nk - K), (0, ne - E), (0, nd - D))
    return RwsetShardState(
        adds=jnp.asarray(np.pad(np.asarray(st.adds), pad3)),
        rmvs=jnp.asarray(np.pad(np.asarray(st.rmvs), pad3)),
        base_vc=jnp.asarray(np.pad(np.asarray(st.base_vc), (0, nd - D))),
        has_base=st.has_base,
        ops=jnp.asarray(ops.reshape(nk * L, -1)),
        valid=jnp.asarray(valid.reshape(-1)),
        n_lanes=L,
    )


# ---------------------------------------------------------------------------
# set_go shard — monotone presence ring (no dots, no cancellation)

# packed columns (set_go): [elem_slot, op_dc, op_ct, op_ss(D)]
_GELEM, _GOPDC, _GOPCT, _GNSCAL = 0, 1, 2, 3


@dataclass
class SetGoShardState:
    """``ops[K*L, 3+D]`` packs [elem_slot, op_dc, op_ct, op_ss(D)];
    the base is a plain presence bitmap (grow-only union)."""

    present: jax.Array   # bool[K, E] base presence
    base_vc: jax.Array   # int[D]
    has_base: jax.Array  # bool[]
    ops: jax.Array       # int[K*L, 3+D]
    valid: jax.Array     # bool[K*L]
    n_lanes: int

    @property
    def _d(self) -> int:
        return self.ops.shape[-1] - _GNSCAL

    def _col(self, c) -> jax.Array:
        return self.ops[:, c].reshape(-1, self.n_lanes)

    @property
    def valid2d(self) -> jax.Array:
        return self.valid.reshape(-1, self.n_lanes)

    @property
    def elem_slot(self):
        return self._col(_GELEM)

    @property
    def op_dc(self):
        return self._col(_GOPDC)

    @property
    def op_ct(self):
        return self._col(_GOPCT)

    @property
    def op_ss(self):
        d = self._d
        return self.ops[:, _GNSCAL:].reshape(-1, self.n_lanes, d)


jax.tree_util.register_dataclass(
    SetGoShardState,
    data_fields=["present", "base_vc", "has_base", "ops", "valid"],
    meta_fields=["n_lanes"],
)


def setgo_shard_init(n_keys: int, n_lanes: int, n_slots: int, n_dcs: int,
                     dtype=jnp.int64) -> SetGoShardState:
    K, L, E, D = n_keys, n_lanes, n_slots, n_dcs
    ops = jnp.zeros((K * L, _GNSCAL + D), dtype=dtype)
    ops = ops.at[:, _GELEM].set(E)
    return SetGoShardState(
        present=jnp.zeros((K, E), dtype=bool),
        base_vc=jnp.zeros((D,), dtype=dtype),
        has_base=jnp.zeros((), dtype=bool),
        ops=ops,
        valid=jnp.zeros((K * L,), dtype=bool),
        n_lanes=L,
    )


@kernel_span("mat.store")
@partial(jax.jit, donate_argnums=(0,))
def setgo_append(st: SetGoShardState, key_idx, lane_off, elem_slot,
                 op_dc, op_ct, op_ss, active: jax.Array | None = None):
    dt = st.ops.dtype
    col = lambda a: a.astype(dt)[:, None]
    rows = jnp.concatenate(
        [col(elem_slot), col(op_dc), col(op_ct), op_ss.astype(dt)],
        axis=1)
    return _scatter_rows(st, key_idx, lane_off, rows, active)


@kernel_span("mat.store")
@partial(jax.jit, donate_argnums=(0,))
def setgo_gc(st: SetGoShardState, gst: jax.Array) -> SetGoShardState:
    cvc = dense.commit_vc(st.op_ss, st.op_dc, st.op_ct)
    stable = st.valid2d & dense.le(cvc, gst[None, None, :])
    present = kernels.setgo_apply(st.present, st.elem_slot, stable)
    return replace(
        st,
        present=present,
        base_vc=jnp.maximum(st.base_vc, gst.astype(st.base_vc.dtype)),
        has_base=jnp.ones((), dtype=bool),
        valid=st.valid & ~stable.reshape(-1),
    )


@kernel_span("mat.store")
@jax.jit
def setgo_read(st: SetGoShardState, read_vc: jax.Array) -> jax.Array:
    """bool[K, E]: grow-only element presence for every key at
    ``read_vc`` in one batched materialization (base bitmap + included
    ring ops) — the full-shard form of :func:`setgo_read_keys`, added
    so every plane type the DevicePlane serves has the same read
    surface (the sharded stores' ``_read_fn`` slot)."""
    K = st.present.shape[0]
    base_vc = jnp.broadcast_to(st.base_vc, (K, st.base_vc.shape[0]))
    has_base = jnp.broadcast_to(st.has_base, (K,))
    mask = kernels.inclusion_mask(
        st.op_dc, st.op_ct, st.op_ss, st.valid2d, base_vc, has_base,
        read_vc)
    return kernels.setgo_apply(st.present, st.elem_slot, mask)


@kernel_span("mat.store")
@jax.jit
def setgo_read_keys(st: SetGoShardState, key_idx: jax.Array,
                    read_vc: jax.Array) -> jax.Array:
    """bool[B, E]: element presence for the requested keys."""
    ops, mask = _gather_key_rows(st, key_idx, read_vc,
                                 _GOPDC, _GOPCT, _GNSCAL)
    return kernels.setgo_apply(
        st.present[key_idx], ops[..., _GELEM], mask)


@kernel_span("mat.store")
@partial(jax.jit, donate_argnums=(0,))
def setgo_purge_keys(st: SetGoShardState,
                     key_idx: jax.Array) -> SetGoShardState:
    L = st.n_lanes
    flat = (key_idx[:, None] * L
            + jnp.arange(L, dtype=key_idx.dtype)).reshape(-1)
    return replace(
        st,
        valid=st.valid.at[flat].set(False, mode="drop"),
        present=st.present.at[key_idx].set(False, mode="drop"),
    )


def setgo_grow(st: SetGoShardState, n_keys: int | None = None,
               n_slots: int | None = None,
               n_dcs: int | None = None) -> SetGoShardState:
    """Host-side capacity regrade (see orset_grow)."""
    K, E = st.present.shape
    D = st._d
    L = st.n_lanes
    nk, ne, nd = (n_keys or K), (n_slots or E), (n_dcs or D)
    if (nk, ne, nd) == (K, E, D):
        return st
    ops = np.asarray(st.ops).reshape(K, L, -1)
    scal = ops[..., :_GNSCAL]
    ss = ops[..., _GNSCAL:]
    ops = np.concatenate(
        [scal, np.pad(ss, ((0, 0), (0, 0), (0, nd - D)))], axis=-1)
    if nk > K:
        ops = np.pad(ops, ((0, nk - K), (0, 0), (0, 0)))
    valid = np.pad(np.asarray(st.valid).reshape(K, L),
                   ((0, nk - K), (0, 0)))
    return SetGoShardState(
        present=jnp.asarray(np.pad(np.asarray(st.present),
                                   ((0, nk - K), (0, ne - E)))),
        base_vc=jnp.asarray(np.pad(np.asarray(st.base_vc), (0, nd - D))),
        has_base=st.has_base,
        ops=jnp.asarray(ops.reshape(nk * L, -1)),
        valid=jnp.asarray(valid.reshape(-1)),
        n_lanes=L,
    )


# ---------------------------------------------------------------------------
# counter_pn shard — same packed-ring machinery, scalar state

# packed columns (counter): [delta, op_dc, op_ct, op_ss(D)]
_CDELTA, _COPDC, _COPCT, _CNSCAL = 0, 1, 2, 3


@dataclass
class CounterShardState:
    """``ops[K*L, 3+D]`` packs [delta, op_dc, op_ct, op_ss(D)]."""

    value: jax.Array     # int[K] base values
    base_vc: jax.Array   # int[D]
    has_base: jax.Array  # bool[]
    ops: jax.Array       # int[K*L, 3+D]
    valid: jax.Array     # bool[K*L]
    n_lanes: int

    @property
    def _d(self) -> int:
        return self.ops.shape[-1] - _CNSCAL

    def _col(self, c) -> jax.Array:
        return self.ops[:, c].reshape(-1, self.n_lanes)

    @property
    def valid2d(self) -> jax.Array:
        return self.valid.reshape(-1, self.n_lanes)

    @property
    def count(self) -> jax.Array:
        return jnp.sum(self.valid2d, axis=1, dtype=jnp.int32)

    @property
    def delta(self):
        return self._col(_CDELTA)

    @property
    def op_dc(self):
        return self._col(_COPDC)

    @property
    def op_ct(self):
        return self._col(_COPCT)

    @property
    def op_ss(self):
        d = self._d
        return self.ops[:, _CNSCAL:].reshape(-1, self.n_lanes, d)


jax.tree_util.register_dataclass(
    CounterShardState,
    data_fields=["value", "base_vc", "has_base", "ops", "valid"],
    meta_fields=["n_lanes"],
)


def counter_shard_init(n_keys: int, n_lanes: int, n_dcs: int,
                       dtype=jnp.int64) -> CounterShardState:
    K, L, D = n_keys, n_lanes, n_dcs
    return CounterShardState(
        value=jnp.zeros((K,), dtype=dtype),
        base_vc=jnp.zeros((D,), dtype=dtype),
        has_base=jnp.zeros((), dtype=bool),
        ops=jnp.zeros((K * L, _CNSCAL + D), dtype=dtype),
        valid=jnp.zeros((K * L,), dtype=bool),
        n_lanes=L,
    )


@kernel_span("mat.store")
@partial(jax.jit, donate_argnums=(0,))
def counter_append(st: CounterShardState, key_idx, lane_off, delta,
                   op_dc, op_ct, op_ss,
                   active: jax.Array | None = None):
    """``active`` (bool[B], optional) drops masked-off ops entirely (no
    scatter, no overflow) — the sharded store's this-chip's-keys filter
    (same contract as orset_append)."""
    dt = st.ops.dtype
    col = lambda a: a.astype(dt)[:, None]
    rows = jnp.concatenate(
        [col(delta), col(op_dc), col(op_ct), op_ss.astype(dt)], axis=1)
    return _scatter_rows(st, key_idx, lane_off, rows, active)


@kernel_span("mat.store")
@partial(jax.jit, donate_argnums=(0,))
def counter_gc(st: CounterShardState, gst: jax.Array) -> CounterShardState:
    cvc = dense.commit_vc(st.op_ss, st.op_dc, st.op_ct)
    stable = st.valid2d & dense.le(cvc, gst[None, None, :])
    value = kernels.counter_read(st.value, st.delta, stable)
    return replace(
        st,
        value=value,
        base_vc=jnp.maximum(st.base_vc, gst.astype(st.base_vc.dtype)),
        has_base=jnp.ones((), dtype=bool),
        valid=st.valid & ~stable.reshape(-1),
    )


@kernel_span("mat.store")
@jax.jit
def counter_read(st: CounterShardState, read_vc: jax.Array) -> jax.Array:
    """int[K]: counter values at ``read_vc``."""
    K = st.value.shape[0]
    base_vc = jnp.broadcast_to(st.base_vc, (K, st.base_vc.shape[0]))
    has_base = jnp.broadcast_to(st.has_base, (K,))
    mask = kernels.inclusion_mask(
        st.op_dc, st.op_ct, st.op_ss, st.valid2d, base_vc, has_base,
        read_vc)
    return kernels.counter_read(st.value, st.delta, mask)


@kernel_span("mat.store")
@jax.jit
def counter_read_keys(st: CounterShardState, key_idx: jax.Array,
                      read_vc: jax.Array) -> jax.Array:
    """int[B]: counter values for just the requested keys at ``read_vc``
    (the transaction read path; see orset_read_keys)."""
    ops, mask = _gather_key_rows(st, key_idx, read_vc,
                                 _COPDC, _COPCT, _CNSCAL)
    return kernels.counter_read(st.value[key_idx], ops[..., _CDELTA], mask)


@kernel_span("mat.store")
@partial(jax.jit, donate_argnums=(0,))
def counter_purge_keys(st: CounterShardState,
                       key_idx: jax.Array) -> CounterShardState:
    """Free ring lanes and zero base values of the given keys (host
    eviction; see orset_purge_keys)."""
    L = st.n_lanes
    flat = (key_idx[:, None] * L
            + jnp.arange(L, dtype=key_idx.dtype)).reshape(-1)
    return replace(
        st,
        valid=st.valid.at[flat].set(False, mode="drop"),
        value=st.value.at[key_idx].set(0, mode="drop"),
    )


def counter_grow(st: CounterShardState, n_keys: int | None = None,
                 n_dcs: int | None = None) -> CounterShardState:
    """Host-side capacity regrade for the counter shard (see orset_grow)."""
    K = st.value.shape[0]
    D = st._d
    L = st.n_lanes
    nk, nd = (n_keys or K), (n_dcs or D)
    if (nk, nd) == (K, D):
        return st
    ops = np.asarray(st.ops).reshape(K, L, -1)
    scal = ops[..., :_CNSCAL]
    ss = ops[..., _CNSCAL:]
    ops = np.concatenate(
        [scal, np.pad(ss, ((0, 0), (0, 0), (0, nd - D)))], axis=-1)
    if nk > K:
        ops = np.pad(ops, ((0, nk - K), (0, 0), (0, 0)))
    valid = np.pad(np.asarray(st.valid).reshape(K, L), ((0, nk - K), (0, 0)))
    return CounterShardState(
        value=jnp.asarray(np.pad(np.asarray(st.value), (0, nk - K))),
        base_vc=jnp.asarray(np.pad(np.asarray(st.base_vc), (0, nd - D))),
        has_base=st.has_base,
        ops=jnp.asarray(ops.reshape(nk * L, -1)),
        valid=jnp.asarray(valid.reshape(-1)),
        n_lanes=L,
    )


def batch_lane_offsets(key_idx: np.ndarray) -> np.ndarray:
    """Host helper: occurrence index of each key within the batch
    (0,1,...) in batch order — disambiguates same-key ops in one append.
    Vectorized (argsort + run-length ranks)."""
    key_idx = np.asarray(key_idx)
    n = len(key_idx)
    if n == 0:
        return np.zeros(0, dtype=np.int32)
    order = np.argsort(key_idx, kind="stable")
    sk = key_idx[order]
    starts = np.r_[0, np.flatnonzero(sk[1:] != sk[:-1]) + 1]
    run_of = np.repeat(np.arange(len(starts)), np.diff(np.r_[starts, n]))
    occ = np.arange(n) - starts[run_of]
    out = np.empty(n, dtype=np.int32)
    out[order] = occ
    return out
