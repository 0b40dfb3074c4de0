"""BASELINE config 3: MV-Register at 64 simulated DCs.

Device path: the *shard store* (antidote_tpu/mat/store.py — the MV
register shares the OR-Set packed ring; mvreg_gc/mvreg_read are the
cross-slot folds), driven like the live data plane: batched appends,
amortized GC folds at the batch frontier, and a full-shard read.  The
hot math is the VC-dominance matrix: every assign carries an observed
VV over 64 DC columns (kernels.mvreg_apply).  Baseline: host
register_mv one-op-at-a-time updates.
"""

import time

import numpy as np

from benches._util import emit, fetch, setup
from antidote_tpu.mat.synth import orset_batch


def device_ops_per_sec(jax, K, B, D, n_steps=8, warmup=2, gc_every=2):
    import jax.numpy as jnp

    from antidote_tpu.mat import store

    rng = np.random.default_rng(0)
    clock = np.zeros(D, dtype=np.int32)
    # the orset stream generator provides causally-plausible assigns
    # (elem_slot = value slot, obs_vv = observed VV); lane offsets are
    # host-precomputed exactly as the device plane amortizes them
    steps = []
    for _ in range(n_steps + warmup):
        s = orset_batch(rng, K, B, D, n_dcs=D, clock=clock,
                        n_elems=4, obs_lag=2)  # match the shard's slots
        s["lane_off"] = store.batch_lane_offsets(s["key_idx"])
        steps.append({k: jax.device_put(jnp.asarray(v))
                      for k, v in s.items()})

    st = store.orset_shard_init(K, n_lanes=8, n_slots=4, n_dcs=D,
                                dtype=jnp.int32)

    def one_step(st, s, do_gc):
        st, _ov = store.orset_append(
            st, s["key_idx"], s["lane_off"], s["elem_slot"], s["is_add"],
            s["dot_dc"], s["dot_seq"], s["obs_vv"], s["op_dc"],
            s["op_ct"], s["op_ss"])
        if do_gc:
            st = store.mvreg_gc(st, s["frontier"])
        return st

    for i, s in enumerate(steps[:warmup]):
        st = one_step(st, s, True)
    fetch(st.dots)
    t0 = time.perf_counter()
    fetch(st.dots)
    oh = time.perf_counter() - t0

    # the timed loop is ONE jitted lax.scan program, so the number is
    # the device's and not the host's per-dispatch cost (scan also
    # mirrors backlog replay)
    stacked = {k: jnp.stack([d[k] for d in steps[warmup:]])
               for k in steps[0]}
    do_gc = jnp.asarray([(i + 1) % gc_every == 0 for i in range(n_steps)])

    @jax.jit
    def run(st, stacked, do_gc):
        def body(st, x):
            s, g = x
            st = one_step(st, s, False)
            st = jax.lax.cond(
                g, lambda t: store.mvreg_gc(t, s["frontier"]),
                lambda t: t, st)
            return st, 0
        st, _ = jax.lax.scan(body, st, (stacked, do_gc))
        return st

    stc = run(st, stacked, do_gc)                  # compile + warm
    fetch(stc.dots)
    fetch(store.mvreg_read(stc, steps[-1]["frontier"]))  # warm the read
    t0 = time.perf_counter()
    stc = run(st, stacked, do_gc)
    dots = store.mvreg_read(stc, steps[-1]["frontier"])
    fetch(dots)
    dt = max(time.perf_counter() - t0 - oh, 1e-9)
    return B * n_steps / dt


def ingest_sweep(jax, K, D, n_coalesced=4096, n_per_op=256,
                 coalesce=(8, 64), gc_every=(2, 8)):
    """ISSUE 4 coalesce x gc_every grid over the mvreg ingest path —
    the BENCH_r05 regression shape made explicit: the legacy per-op
    leg appends ONE op per dispatch through the per-column path (1
    kernel dispatch + ~10 H2D transfers per op, each padded to the
    64-row bucket), the coalesced legs flush C ops as ONE packed
    tensor (mat/ingest.py) with the mvreg GC fold cadence decoupled
    (every ``gc_every`` flushes — the headline sweep's amortized-GC
    recipe).

    "Dispatches" count kernel launches PLUS H2D transfers: each upload
    is its own host->device round trip, which is exactly what made the
    per-op path scatter-bound.
    Returns (rows for emit, detail grid)."""
    import jax.numpy as jnp

    from antidote_tpu.mat import ingest, store
    from antidote_tpu.mat.device_plane import _pack_rows

    rng = np.random.default_rng(0)
    cols = ("s", "s", "s", "s", "vv", "s", "s", "vv")
    perm = ingest.PACKED_PERMS["orset_append"]
    E = 4

    def gen_rows(n):
        """Decoded mvreg rows (the device plane's staging tuples):
        monotone per-DC commit stamps, one-pair observed/snapshot VCs."""
        out = []
        ct = np.zeros(D, dtype=np.int64)
        for i in range(n):
            dc = int(rng.integers(0, D))
            ct[dc] += 1
            out.append((int(rng.integers(0, K)),
                        int(rng.integers(0, E)), 1, dc, int(ct[dc]),
                        [(dc, max(int(ct[dc]) - 2, 0))], dc,
                        int(ct[dc]), [(dc, int(ct[dc]))]))
        return out

    def frontier(rows):
        f = np.zeros(D, dtype=np.int64)
        for r in rows:
            f[r[6]] = max(f[r[6]], r[7])
        return jnp.asarray(f)

    # ---- legacy per-op leg: one op per dispatch, per-column uploads
    rows = gen_rows(n_per_op)
    st = store.orset_shard_init(K, n_lanes=8, n_slots=E, n_dcs=D,
                                dtype=jnp.int32)
    legacy_bytes = legacy_disp = 0
    # warm (compile) outside the timed loop
    ki, lo, arrays = _pack_rows(rows[:1], K, D, cols)
    st, _ = store.orset_append(st, jnp.asarray(ki), jnp.asarray(lo),
                               *(jnp.asarray(a) for a in arrays))
    fetch(st.dots)
    t0 = time.perf_counter()
    for r in rows[1:]:
        ki, lo, arrays = _pack_rows([r], K, D, cols)
        st, _ = store.orset_append(
            st, jnp.asarray(ki), jnp.asarray(lo),
            *(jnp.asarray(a) for a in arrays))
        legacy_bytes += ki.nbytes + lo.nbytes + sum(
            a.nbytes for a in arrays)
        legacy_disp += 1 + 2 + len(arrays)  # kernel + each upload
    st = store.mvreg_gc(st, frontier(rows))
    legacy_disp += 1
    fetch(st.dots)
    legacy_dt = time.perf_counter() - t0
    legacy = dict(
        ops_per_dispatch=round((n_per_op - 1) / legacy_disp, 4),
        h2d_bytes_per_op=round(legacy_bytes / (n_per_op - 1), 1),
        ops_per_sec=round((n_per_op - 1) / max(legacy_dt, 1e-9)))

    # ---- coalesced legs: C ops per packed flush, fold every G flushes
    grid = {}
    best = None
    for C in coalesce:
        rows = gen_rows(n_coalesced)
        for G in gc_every:
            st = store.orset_shard_init(K, n_lanes=8, n_slots=E,
                                        n_dcs=D, dtype=jnp.int32)
            chunks = [rows[i:i + C] for i in range(0, len(rows), C)]
            packed0 = ingest.pack_rows(chunks[0], K, D, cols, perm)
            st, _ = ingest.packed_append(st, jnp.asarray(packed0))
            fetch(st.dots)  # warm compile outside the timed loop
            nbytes = ndisp = nops = 0
            t0 = time.perf_counter()
            for i, chunk in enumerate(chunks[1:]):
                packed = ingest.pack_rows(chunk, K, D, cols, perm)
                st, _ = ingest.packed_append(st, jnp.asarray(packed))
                nbytes += packed.nbytes
                ndisp += 2  # the kernel + its ONE upload
                nops += len(chunk)
                if (i + 1) % G == 0:
                    st = store.mvreg_gc(st, frontier(chunk))
                    ndisp += 1
            fetch(st.dots)
            dt = max(time.perf_counter() - t0, 1e-9)
            cell = dict(ops_per_dispatch=round(nops / ndisp, 2),
                        h2d_bytes_per_op=round(nbytes / nops, 1),
                        ops_per_sec=round(nops / dt))
            grid[f"c{C}_g{G}"] = cell
            # the GATED cell is the max-ops/dispatch one: that ratio is
            # a deterministic function of the grid (counts and shapes,
            # no timing), so bench_gate diffs a stable value — picking
            # by measured ops/s would let run-to-run timing noise swing
            # which cell wins and fail the gate spuriously (the ops/s
            # ordering stays visible in the emitted grid detail)
            if best is None or cell["ops_per_dispatch"] \
                    > best[1]["ops_per_dispatch"]:
                best = (f"c{C}_g{G}", cell)
    return legacy, grid, best


def host_ops_per_sec(n_ops=20_000, D=64):
    from antidote_tpu.crdt import get_type

    cls = get_type("register_mv")
    rng = np.random.default_rng(1)
    st = cls.new()
    t0 = time.perf_counter()
    for i in range(n_ops):
        dc = int(rng.integers(0, D))
        obs = tuple(d for d, _v in st)
        st = cls.update(("asgn", b"v%d" % (i % 7), (dc, i + 1), obs), st)
    return n_ops / (time.perf_counter() - t0)


def main():
    quick, jax = setup()
    K = 262_144 if not quick else 16_384
    B = 32_768 if not quick else 4_096
    dev = device_ops_per_sec(jax, K, B, D=64)
    host = host_ops_per_sec()
    emit("mvreg_assign_merges_per_sec_64dc", round(dev), "ops/s",
         round(dev / host, 2), keys=K, batch=B, dcs=64,
         path="shard store (append + mvreg_gc + mvreg_read)",
         device=str(jax.devices()[0]), host_baseline=round(host))
    # ISSUE 4: the coalesce x gc sweep over the ingest plane — the
    # directional rows bench_gate diffs (ops/dispatch up, B/op down),
    # with the legacy per-op leg as the in-row baseline
    legacy, grid, best = ingest_sweep(
        jax, K=16_384 if quick else 65_536, D=64,
        n_coalesced=2048 if quick else 8192,
        n_per_op=192 if quick else 512)
    emit("mvreg_ingest_ops_per_dispatch",
         best[1]["ops_per_dispatch"], "ops/dispatch",
         round(best[1]["ops_per_dispatch"]
               / max(legacy["ops_per_dispatch"], 1e-9), 1),
         best_cell=best[0], legacy=legacy, grid=grid,
         note="dispatches = kernel launches + H2D transfers; legacy = "
              "per-op per-column appends (the BENCH_r05 regression "
              "shape), coalesced = packed single-upload flushes with "
              "decoupled mvreg_gc cadence")
    emit("mvreg_ingest_h2d_bytes_per_op",
         best[1]["h2d_bytes_per_op"], "b/op",
         round(legacy["h2d_bytes_per_op"]
               / max(best[1]["h2d_bytes_per_op"], 1e-9), 1),
         best_cell=best[0], legacy=legacy)


if __name__ == "__main__":
    main()
