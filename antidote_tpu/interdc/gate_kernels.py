"""Jitted kernels of the device-resident dependency-gate ring.

ISSUE 3: the batched gate path used to re-pack every queued txn into
fresh host arrays, upload six tensors, and fetch three back on EVERY
``process_queues`` call — worst-case repack cost per delivery.  These
kernels keep the gate state resident instead: a padded ring of
dependency rows that is appended to incrementally (one small H2D
scatter per batch of arrivals, ring buffers donated so the update is
in-place), retired/compacted in place, and driven by a fixpoint whose
only mandatory fetch is a scalar applied-count.

Ring layout (all arrays ``cap`` rows; ``d_pad`` dense clock columns):

- ``ss``     int64[cap, d_pad]  snapshot VC of each queued txn
- ``origin`` int32[cap]         dense column of the txn's origin DC
- ``pos``    int32[cap]         per-origin FIFO position (monotone)
- ``ts``     int64[cap]         commit timestamp
- ``live``   bool[cap]          slot holds a still-queued txn; dead
                                and never-used slots are inert in
                                every kernel (no sentinel rows needed)

Heartbeat stamps are not rows: the gate keeps the newest stamp of each
origin and hands them to the fixpoint as one ``int64[d_pad]`` vector
(:func:`fixpoint` states the watermark rule they feed).

Host-side slot bookkeeping (mirror queues, free list, column map)
lives in :class:`antidote_tpu.interdc.dep._DeviceRing`; these kernels
are pure array programs.  Every public entry point carries
``@kernel_span`` (tools/trace_lint.py now enforces the rule for
antidote_tpu/interdc/ as well as mat/).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from antidote_tpu.clocks import dense
from antidote_tpu.obs.prof import kernel_span

#: FIFO-position infinity: larger than any real queue position, small
#: enough that +1 arithmetic cannot overflow int32
BIG_POS = np.int32(np.iinfo(np.int32).max // 2)


@kernel_span("interdc.dep")
@partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))
def ring_append(ss, origin, pos, ts, live,
                slots, u_ss, u_origin, u_pos, u_ts):
    """Scatter a padded batch of arrivals into ring ``slots``.

    Update rows are padded to a power-of-two batch (bounding the jit
    cache); padding rows carry ``slots == cap`` which ``mode="drop"``
    discards.  The five ring buffers are donated — an append updates
    the resident state in place, no copy."""
    ss = ss.at[slots].set(u_ss, mode="drop")
    origin = origin.at[slots].set(u_origin, mode="drop")
    pos = pos.at[slots].set(u_pos, mode="drop")
    ts = ts.at[slots].set(u_ts, mode="drop")
    live = live.at[slots].set(True, mode="drop")
    return ss, origin, pos, ts, live


@kernel_span("interdc.dep")
@partial(jax.jit, donate_argnums=(0,))
def ring_retire(live, slots):
    """Mark ``slots`` dead (txns popped outside the ring replay: the
    host walk ran in between, or a wave aborted on PartitionRetired).
    Padding slots carry ``cap`` and are dropped."""
    return live.at[slots].set(False, mode="drop")


@kernel_span("interdc.dep")
@partial(jax.jit, static_argnames=("new_d",))
def ring_gather(ss, origin, pos, ts, idx, n_live, new_d):
    """Re-layout the ring through a device-side gather: grow capacity
    (``idx`` longer than the ring), shrink it (lazy compaction once
    dead slots exceed the threshold), or widen the clock domain
    (``new_d`` > current width; new columns read 0 = the dense
    missing-entry semantics).  ``idx[i]`` is the OLD slot written to
    new slot i; rows at or past ``n_live`` come out dead.  No H2D
    beyond the index vector itself — the resident rows never round-
    trip through the host."""
    if new_d > ss.shape[1]:
        ss = jnp.pad(ss, ((0, 0), (0, new_d - ss.shape[1])))
    ss = ss[idx]
    origin = origin[idx]
    pos = pos[idx]
    ts = ts[idx]
    live = jnp.arange(idx.shape[0], dtype=jnp.int32) < n_live
    return ss, origin, pos, ts, live


def fixpoint(ss, origin, pos, ts, live, pvc, stamp):
    """Iterate-until-stable over the LIVE rows: returns ``(applied
    bool[N], round int32[N], final partition clock int64[D])``.  The
    traced body of both jitted gate programs (:func:`ring_fixpoint`
    here, ``dep.gate_fixpoint`` for the repack form).

    Each round evaluates, data-parallel over all N queued txns:
      ready    = pvc >= deps                     (the origin's own
                 column zeroed, reference try_store,
                 src/inter_dc_dep_vnode.erl:131-136)
      applied  = ready and FIFO-prefix           (a txn applies only if
                 every earlier txn of its origin queue applies — the
                 per-origin min position of a not-ready txn bounds it)
      pvc      = THE WATERMARK RULE, per origin: the newest stamp
                 received from it, lowered to the smallest commit time
                 among its rows not applied, less one (both bounds are
                 exclusive), never backwards.
    and repeats while pvc still advances.  A watermark is a promise
    only the origin can make (its stamp is its min-prepared time: no
    txn of the stream commits below it any more, and all that did are
    on the wire ahead of it); an applied txn's commit time promises
    nothing, because a stream arrives in LOG order and a smaller
    commit time may still be behind it.  What a receiver may add is
    the bound: a txn it holds unapplied is not visible, whatever the
    stamp says.  ``DependencyGate._raise_watermarks`` is the same rule
    on the host; tests/unit/test_dep_gate.py holds both to one table.

    Terminates because applied/pvc are monotone; the round count is
    bounded by the longest dependency chain through the queues.
    ``round[i]`` is the round at which txn i became applicable: its
    dependencies were met by the clock of round r-1, so it cannot
    depend on another round-r txn, and replaying applies sorted by
    (round, fifo pos) is causally safe."""
    d = pvc.shape[0]
    n = ss.shape[0]
    big = jnp.asarray(np.iinfo(np.int32).max, jnp.int32)
    far = jnp.asarray(np.iinfo(np.int64).max, ts.dtype)
    deps = dense.set_dc(ss, origin, 0)

    def round_(pvc):
        ready = live & dense.ge(pvc, deps)                   # [N]
        # dead rows neither block (pos -> +inf) nor bound anything
        notready_pos = jnp.where(ready | ~live, big, pos)
        blocked_min = jnp.full((d,), big, jnp.int32).at[origin].min(
            notready_pos, mode="drop")
        applied = ready & (pos < blocked_min[origin])
        low = jnp.full((d,), far, ts.dtype).at[origin].min(
            jnp.where(live & ~applied, ts, far), mode="drop")
        return applied, jnp.maximum(pvc, jnp.minimum(stamp, low) - 1)

    def note_round(rounds, applied, r):
        newly = applied & (rounds < 0)
        return jnp.where(newly, r, rounds)

    def cond(carry):
        _, _, _, changed = carry
        return changed

    def body(carry):
        rounds, pvc, r, _ = carry
        applied, new_pvc = round_(pvc)
        rounds = note_round(rounds, applied, r)
        return (rounds, new_pvc, r + 1, jnp.any(new_pvc != pvc))

    rounds0 = jnp.full((n,), -1, jnp.int32)
    rounds, pvc, r, _ = jax.lax.while_loop(
        cond, body,
        (rounds0, pvc, jnp.asarray(0, jnp.int32), jnp.asarray(True)))
    # the loop exits after a round that did not advance pvc; evaluate
    # once more at the stable clock (no-progress-first-round case)
    applied, _ = round_(pvc)
    return applied, note_round(rounds, applied, r), pvc


@kernel_span("interdc.dep")
@jax.jit
def ring_fixpoint(ss, origin, pos, ts, live, pvc, stamp):
    """:func:`fixpoint` over the resident ring.  Returns ``(applied
    bool[cap], round int32[cap], final pvc int64[D], new_live
    bool[cap], applied_count int32)``.  The caller's only mandatory
    fetch is the scalar count; the dense mask and rounds are fetched
    once per admission wave, and ``new_live`` (= live minus the
    applied set) stays on device as the next resident live mask when
    the wave replays completely."""
    applied, rounds, pvc = fixpoint(ss, origin, pos, ts, live, pvc, stamp)
    return (applied, rounds, pvc, live & ~applied,
            jnp.sum(applied, dtype=jnp.int32))


def ring_alloc(cap: int, d_pad: int):
    """Fresh all-dead ring buffers, created ON DEVICE (``jnp.zeros``
    lowers to a device fill — a rebuild uploads nothing)."""
    return (jnp.zeros((cap, d_pad), dtype=jnp.int64),
            jnp.zeros((cap,), dtype=jnp.int32),
            jnp.zeros((cap,), dtype=jnp.int32),
            jnp.zeros((cap,), dtype=jnp.int64),
            jnp.zeros((cap,), dtype=bool))
