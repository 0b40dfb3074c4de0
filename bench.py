"""Benchmark: OR-Set update-heavy materialization at 1M keys (BASELINE
config 2, the headline metric: CRDT merges/sec/chip).

Device path: the batched shard store (antidote_tpu/mat/store.py) applies
committed-op batches to a 1M-key OR-Set shard resident on one TPU chip —
append + GST fold (GC) + read, all as fused XLA programs.  The append
uses the exact occurrence-disambiguated lane placement
(store.batch_lane_offsets, computed host-side outside the timed loop,
exactly as a deployment amortizes it into batch assembly); the
full-shard read flag-selects the Pallas fused kernel
(mat/pallas_kernels.py orset_read_packed) next to the jnp reference
path so both latencies are recorded.

Baseline: the reference executes this per key per op inside BEAM
gen_servers (reference src/clocksi_materializer.erl hot loop).  The
reference publishes no numbers (BASELINE.md) and this image has no
Erlang runtime, so the BEAM yardstick is *bounded*, not guessed: the
same per-op apply loop is measured twice — once through the host Python
CRDT type, and once as native C++ (antidote_tpu/native/
orset_baseline.cpp).  BEAM sits between the two (faster than CPython,
slower than C++ at per-op hash-map work), so ``vs_baseline`` reports the
device against the *C++* loop — a conservative lower bound on the true
device-vs-BEAM ratio.  The Python ratio is kept in ``detail``.

Timing: dependent-chain methodology (benches/_util.py) — device steps
are chained and a final scalar fetch is the completion barrier (its
round-trip cost measured separately and subtracted).

One process holds the chip: every leg that needs it runs in this
process.  Without a TPU the bench exits non-zero; ``--cpu`` is the
explicit logic-check mode and names its platform in the metric.  A leg
that raises ends the bench with its traceback.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
"""

import contextlib
import ctypes
import importlib
import io
import json
import os
import subprocess
import sys
import time

import numpy as np

from benches._util import fetch, setup


def build_stream(K, B, n_steps, D, n_dcs, rng):
    """Synthetic committed add/remove stream, pre-chunked into batches
    (shared generator: antidote_tpu/mat/synth.py) with host-precomputed
    lane offsets (occurrence-disambiguated same-key placement)."""
    from antidote_tpu.mat import store
    from antidote_tpu.mat.synth import orset_batch

    clock = np.zeros(n_dcs, dtype=np.int32)
    steps = []
    for _ in range(n_steps):
        s = orset_batch(rng, K, B, D, n_dcs, clock, obs_lag=2)
        s["lane_off"] = store.batch_lane_offsets(s["key_idx"])
        steps.append(s)
    return steps


#: the headline shard shape (BASELINE config 2) — chip_smoke.py checks
#: the Pallas read at EXACTLY the shape bench.py reports
HEADLINE_SHAPE = dict(K=1_000_000, B=65_536, D=8, n_dcs=3, warmup=2)


def headline_sweep(n_steps, gc_every=4):
    """name -> (coalesce, gc_every, n_appends, with_reads, seed): the
    coalescing-variant sweep bench_device runs (reads ride on b4's
    final state).

    Each variant carries its OWN deterministic rng seed, so a variant's
    op stream does not depend on which variants ran before it.  b1
    keeps seed 0: a fresh rng(0) is exactly the stream the historic
    thread-through gave it."""
    return {
        "b1": (1, gc_every, n_steps, False, 0),
        "b4": (4, 3, max(n_steps // 4, 3), True, 4),
        "b8": (8, 2, max(n_steps // 8, 2), False, 8),
    }


def bench_variant(K, B, D, n_dcs, warmup, rng,
                  coalesce, gc_every_v, n_appends):
    """One coalescing-variant run of BASELINE config 2 (see
    bench_device).  Returns (variant dict, final state, last frontier,
    fetch overhead)."""
    import jax
    import jax.numpy as jnp

    from antidote_tpu.mat import store

    bb = B * coalesce
    steps = build_stream(K, bb, n_appends + warmup, D, n_dcs, rng)
    st = store.orset_shard_init(K, n_lanes=8, n_slots=8, n_dcs=D,
                                dtype=jnp.int32)

    def put(s):
        return {k: jax.device_put(jnp.asarray(v))
                for k, v in s.items()}

    dev_steps = [put(s) for s in steps]

    def one_step(st, s, do_gc):
        st, ov = store.orset_append(
            st, s["key_idx"], s["lane_off"], s["elem_slot"],
            s["is_add"], s["dot_dc"], s["dot_seq"], s["obs_vv"],
            s["op_dc"], s["op_ct"], s["op_ss"])
        if do_gc:
            # amortized fold at the batch frontier (the reference
            # GCs per key every ?OPS_THRESHOLD ops — also
            # amortized); L lanes absorb gc_every appends of
            # per-key arrivals
            st = store.orset_gc(st, s["frontier"])
        return st, ov

    for s in dev_steps[:warmup]:
        st, _ = one_step(st, s, True)
    fetch(st.dots)

    stacked = {k: jnp.stack([d[k] for d in dev_steps[warmup:]])
               for k in dev_steps[0]}
    do_gc = jnp.asarray(
        [(i + 1) % gc_every_v == 0 for i in range(n_appends)])

    @jax.jit
    def run(st, stacked, do_gc):
        def body(st, x):
            s, g = x
            st, ov = store.orset_append(
                st, s["key_idx"], s["lane_off"], s["elem_slot"],
                s["is_add"], s["dot_dc"], s["dot_seq"], s["obs_vv"],
                s["op_dc"], s["op_ct"], s["op_ss"])
            st = jax.lax.cond(
                g, lambda t: store.orset_gc(t, s["frontier"]),
                lambda t: t, st)
            return st, jnp.sum(ov)
        return jax.lax.scan(body, st, (stacked, do_gc))

    stc, ov = run(st, stacked, do_gc)          # compile + warm run
    fetch(stc.dots)
    t0 = time.perf_counter()
    fetch(stc.dots)
    fetch_oh = time.perf_counter() - t0
    best = None
    for _ in range(2):
        t0 = time.perf_counter()
        stc, ov = run(st, stacked, do_gc)
        fetch(stc.dots)
        dt = max(time.perf_counter() - t0 - fetch_oh, 1e-9)
        best = dt if best is None else min(best, dt)
    # dropped (overflowed) ops were never merged: they do not count
    # toward the rate, and a variant that sheds load cannot win on
    # the shed ops
    dropped = int(np.sum(np.asarray(ov)))
    n_ops = bb * n_appends - dropped
    return {
        "coalesce": coalesce, "batch_rows": bb,
        "gc_every": gc_every_v, "appends": n_appends,
        "ops": n_ops, "seconds": round(best, 4),
        "overflow_dropped": dropped,
        "ops_per_sec": n_ops / best,
    }, stc, dev_steps[-1]["frontier"], fetch_oh



def bench_reads(stc, frontier, fetch_oh, n_reads=10):
    """Full-shard read latency on a built store state, chained on
    itself so each read depends on the last — measured through the jnp
    reference path and, on a TPU, both Pallas fused variants (a kernel
    the chip's compiler refuses raises)."""
    import jax
    import jax.numpy as jnp

    from antidote_tpu.mat import store

    def chain_read(read_fn):
        def one_read(present):
            # numerically `frontier` (presence is non-negative) but XLA
            # cannot prove it, so reads form a dependent chain
            vc = frontier + jnp.minimum(present[0, 0].astype(jnp.int32), 0)
            return read_fn(stc, vc)

        p = read_fn(stc, frontier)
        fetch(p)
        t0 = time.perf_counter()
        for _ in range(n_reads):
            p = one_read(p)
        fetch(p)
        return max(time.perf_counter() - t0 - fetch_oh, 1e-9) / n_reads

    read_jnp = chain_read(store.orset_read)
    if jax.default_backend() != "tpu":
        return read_jnp, None, None  # --cpu: no Mosaic, nothing to time

    def fused_read(variant):
        return chain_read(
            lambda s_, vc: store.orset_read_full(s_, vc, fused=variant))

    return read_jnp, fused_read(True), fused_read("hybrid")


def bench_device(K, B, n_steps, D, n_dcs, warmup=2, gc_every=4):
    """Returns (best_variant_dict, read_jnp, read_fused, read_hybrid).

    Round-5 methodology (measured on a v5e; record removed in PR 21):
    - the per-batch XLA scatter costs ~200 ns/row SERIALIZED and is the
      dominant term, but scales sub-linearly in batch size (65k rows
      13.5 ms, 262k rows 30 ms) — so the bench also measures the
      COALESCED configuration the production flusher reaches under
      load (mat/device_plane.py batches pending commit groups per
      flush), where each device append carries several stream chunks;
    - the whole timed loop is ONE jitted lax.scan program, so the
      number is the device's and not the per-dispatch cost of the
      host, and scan also mirrors how the plane replays a backlog;
    - overflow (ops dropped for lane pressure) is fetched and reported
      — a coalescing level is only honest while overflow stays ~0.

    Variants: (coalesce=1, gc_every=4) is the historic configuration;
    (coalesce=4, gc_every=3) and
    (coalesce=8, gc_every=2) trade scatter count against per-key lane
    load (the deepest level rides ~1 op/key mean between folds at 1M
    keys).  The headline is the fastest; all land in the detail
    dict."""
    import jax
    import jax.numpy as jnp

    from antidote_tpu.mat import store

    def run_variant(coalesce, gc_every_v, n_appends, _reads, seed):
        # per-variant rng from the sweep's own seed
        return bench_variant(K, B, D, n_dcs, warmup,
                             np.random.default_rng(seed),
                             coalesce, gc_every_v, n_appends)

    sweep = headline_sweep(n_steps, gc_every)
    # coalesced levels trade scatter count against per-key lane load
    # (XLA scatter is serialized per row but sublinear in batch size);
    # overflow is deducted and reported.  Non-reads variants drop
    # their ~1 GB final state immediately.
    v1 = run_variant(*sweep["b1"])[0]
    v8 = run_variant(*sweep["b8"])[0]
    v4, stc, frontier, fetch_oh = run_variant(*sweep["b4"])
    allv = (v1, v4, v8)
    variants = {"b%d_gc%d" % (v["batch_rows"], v["gc_every"]): v
                for v in allv}
    bestv = max(allv, key=lambda v: v["ops_per_sec"])
    bestv = dict(bestv, variants=variants)

    read_jnp, read_fused, read_hybrid = bench_reads(stc, frontier,
                                                    fetch_oh)
    return bestv, read_jnp, read_fused, read_hybrid


def _baseline_stream(n_ops, rng, K, n_elems=8, n_dcs=3):
    keys = rng.integers(0, K, size=n_ops)
    adds = rng.random(n_ops) < 0.7
    els = rng.integers(0, n_elems, size=n_ops)
    dcs = rng.integers(0, n_dcs, size=n_ops)
    seqs = np.arange(1, n_ops + 1, dtype=np.int64)
    return keys, adds, els, dcs, seqs


def bench_host_baseline(K, n_ops=30_000):
    """BEAM-style apply-one-op-at-a-time loop through the host CRDT type
    (CPython: the *lower* bracket of the BEAM bound).  Same K-key space
    as the device bench, so the hash-map working set is comparable."""
    from antidote_tpu.crdt import get_type

    cls = get_type("set_aw")
    rng = np.random.default_rng(1)
    states = {}
    elems = [b"a", b"b", b"c", b"d", b"e", b"f", b"g", b"h"]
    keys, adds, els, dcs, seqs = _baseline_stream(n_ops, rng, K)
    t0 = time.perf_counter()
    for i in range(n_ops):
        k = int(keys[i])
        stt = states.get(k)
        if stt is None:
            stt = cls.new()
        e = elems[int(els[i])]
        dot = (int(dcs[i]), int(seqs[i]))
        if adds[i]:
            eff = ("add", ((e, dot, tuple(stt.get(e, ()))),))
        else:
            eff = ("rmv", ((e, tuple(stt.get(e, ()))),))
        states[k] = cls.update(eff, stt)
    dt = time.perf_counter() - t0
    return n_ops / dt


def bench_cpp_baseline(K, n_ops=2_000_000):
    """The same per-op loop as native C++ (the *upper* bracket: BEAM
    cannot beat this at per-op hash-map work) over the same K-key space
    as the device bench.  None if g++ is absent."""
    from antidote_tpu.native.build import ensure_built

    so = ensure_built("orset_baseline")
    if so is None:
        return None
    lib = ctypes.CDLL(so)
    lib.orset_baseline_run.restype = ctypes.c_double
    lib.orset_baseline_run.argtypes = [
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_uint8),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
    ]
    rng = np.random.default_rng(1)
    keys, adds, els, dcs, seqs = _baseline_stream(n_ops, rng, K)
    keys = np.ascontiguousarray(keys, dtype=np.int64)
    adds = np.ascontiguousarray(adds, dtype=np.uint8)
    els = np.ascontiguousarray(els, dtype=np.int32)
    dcs = np.ascontiguousarray(dcs, dtype=np.int32)
    seqs = np.ascontiguousarray(seqs, dtype=np.int64)
    live = ctypes.c_int64(0)
    ptr = lambda a, t: a.ctypes.data_as(ctypes.POINTER(t))
    best = None
    for _ in range(3):  # min over runs: one-shot timing is noisy
        dt = lib.orset_baseline_run(
            n_ops, ptr(keys, ctypes.c_int64), ptr(adds, ctypes.c_uint8),
            ptr(els, ctypes.c_int32), ptr(dcs, ctypes.c_int32),
            ptr(seqs, ctypes.c_int64), ctypes.byref(live))
        best = dt if best is None else min(best, dt)
    return n_ops / best


def _config_extras(quick_cpu: bool, quick: bool = False) -> dict:
    """Driver-visible summaries of the other BASELINE configs, folded
    into the single JSON line's detail.

    Configs 1, 3, 4 and 5 run IN THIS PROCESS on the bench platform: a
    chip belongs to one process, and this one already holds it, so a
    child started now could never reach the device.  Config 6 (the
    served txn/s leg) is a child pinned to the CPU by design — it
    spawns a multi-process DC, which needs one chip per member — and
    its key says so; a served leg with the device in it is ROADMAP
    1.1."""
    import jax

    from benches.config5_gst import summary as gst_summary

    out = dict(gst_summary(jax, N=64 if quick_cpu else 256))
    out.pop("vs_host_round", None)

    r = subprocess.run(
        [sys.executable, "-m", "benches.config6_txn", "--cpu", "--quick"],
        timeout=900, capture_output=True, text=True, check=True,
        cwd=os.path.dirname(os.path.abspath(__file__)))
    cfg6 = json.loads([l for l in r.stdout.splitlines()
                       if l.startswith("{")][-1])
    out["txn_per_sec_8client_cpu_quick"] = cfg6["value"]
    for key, field in (
            ("txn_p50_ms", "p50_ms"), ("txn_p99_ms", "p99_ms"),
            ("txn_p50_1t_ms", "p50_1t_ms"), ("txn_p99_1t_ms", "p99_1t_ms"),
            ("txn_latency_starved", "latency_starved"),
            ("txn_pb_per_sec", "pb_txn_per_sec"),
            ("txn_pb_starved", "pb_starved"),
            ("txn_cluster_per_sec", "cluster_txn_per_sec"),
            # topology honesty (round-4 verdict): how many cores backed
            # the serving rows, and the scale-out ratio (or the starved
            # marker explaining its absence)
            ("cpu_count", "cpu_count"),
            ("cluster_starved", "cluster_starved"),
            ("cluster_scaling", "cluster_scaling"),
            ("cluster_rpc_latency", "cluster_rpc_latency")):
        out[key] = cfg6["detail"].get(field)

    # configs 1/3/4: quick on CPU (logic validation), FULL size on
    # hardware — at quick sizes the tiny device programs measure the
    # per-dispatch cost of the host, not the chip (round-5: quick on
    # the chip recorded rga 679 ops/s vs 13k on CPU).  An explicit
    # --quick still stays quick even on hardware.
    flags = (["--cpu", "--quick"] if quick_cpu
             else (["--quick"] if quick else []))
    for key, mod in (("counter", "benches.config1_counter"),
                     ("mvreg_64dc", "benches.config3_mvreg"),
                     ("rga_steady", "benches.config4_rga")):
        cfg = _run_config_here(mod, flags)
        out[f"{key}_value"] = cfg["value"]
        out[f"{key}_unit"] = cfg["unit"]
        out[f"{key}_vs_baseline"] = cfg["vs_baseline"]
    return out


def _run_config_here(mod: str, flags: list) -> dict:
    """Run a config's ``main()`` in this process (it reads its flags
    from sys.argv and prints JSON lines) and return its first — its
    headline — metric."""
    buf = io.StringIO()
    argv, sys.argv = sys.argv, [mod, *flags]
    try:
        with contextlib.redirect_stdout(buf):
            importlib.import_module(mod).main()
    finally:
        sys.argv = argv
    return json.loads(next(l for l in buf.getvalue().splitlines()
                           if l.startswith("{")))


def main():
    quick, jax = setup()
    K = 1_000_000 if not quick else 65_536
    B = 65_536 if not quick else 8_192
    n_steps = 20 if not quick else 4
    bestv, read_jnp, read_fused, read_hybrid = bench_device(
        K=K, B=B, n_steps=n_steps, D=8, n_dcs=3)
    dev_ops = bestv["ops_per_sec"]
    host_ops = bench_host_baseline(K)
    cpp_ops = bench_cpp_baseline(K, 200_000 if quick else 2_000_000)
    # BEAM sits between CPython and C++ at this workload; the C++ ratio
    # is the conservative (defensible) headline
    vs = dev_ops / cpp_ops if cpp_ops else dev_ops / host_ops
    extras = _config_extras(quick_cpu="--cpu" in sys.argv, quick=quick)
    dev = jax.devices()[0]
    ms = lambda t: None if t is None else round(t * 1e3, 2)
    print(json.dumps({
        # the device metric's name belongs to a full-size chip run
        "metric": ("orset_update_merges_per_sec_per_chip_1M_keys"
                   if dev.platform == "tpu" and not quick else
                   f"orset_update_merges_per_sec_{dev.platform}"
                   "_logic_check"),
        "value": round(dev_ops),
        "unit": "merges/s",
        "vs_baseline": round(vs, 2),
        "detail": {
            "platform": dev.platform, "device_kind": dev.device_kind,
            "keys": K, "batch": B, "steps": n_steps,
            "headline_variant": {k: v for k, v in bestv.items()
                                 if k != "variants"},
            "variants": bestv["variants"],
            "full_shard_read_ms": ms(read_jnp),
            "full_shard_read_fused_ms": ms(read_fused),
            "full_shard_read_hybrid_ms": ms(read_hybrid),
            "host_python_merges_per_sec": round(host_ops),
            "host_cpp_merges_per_sec": round(cpp_ops) if cpp_ops else None,
            "vs_python_baseline": round(dev_ops / host_ops, 2),
            "baseline_note": (
                "no Erlang runtime in image; BEAM per-op loop is "
                "bracketed by [CPython, C++] — vs_baseline uses the "
                + ("C++" if cpp_ops else "CPython (g++ unavailable)")
                + " bracket (per core; x%d cores for a machine-wide "
                "bound)" % (os.cpu_count() or 1)),
            **extras,
        },
    }))


if __name__ == "__main__":
    main()
