"""Fused pallas OR-Set read vs the jnp kernels path.  Every kernel call
here asks for interpret mode by name: the suite runs on the CPU, and
nothing in store.py selects interpret mode on its own.  The same kernels
compiled by Mosaic are checked on the chip by chip_smoke.py."""

import numpy as np
import jax.numpy as jnp
import pytest

from antidote_tpu.mat import kernels, pallas_kernels, store
from antidote_tpu.mat.synth import orset_batch


def reference_read(st, read_vc):
    return np.asarray(store.orset_read(st, read_vc))


@pytest.mark.parametrize("seed", range(3))
def test_matches_jnp_path(seed):
    K, B, D, n_dcs = 256, 512, 8, 3
    rng = np.random.default_rng(seed)
    clock = np.zeros(n_dcs, dtype=np.int32)
    st = store.orset_shard_init(K, n_lanes=8, n_slots=8, n_dcs=D,
                                dtype=jnp.int32)
    for _ in range(3):
        s = orset_batch(rng, K, B, D, n_dcs, clock, obs_lag=2)
        lane = jnp.asarray(store.batch_lane_offsets(s["key_idx"]))
        st, _ = store.orset_append(
            st, jnp.asarray(s["key_idx"]), lane,
            jnp.asarray(s["elem_slot"]), jnp.asarray(s["is_add"]),
            jnp.asarray(s["dot_dc"]), jnp.asarray(s["dot_seq"]),
            jnp.asarray(s["obs_vv"]), jnp.asarray(s["op_dc"]),
            jnp.asarray(s["op_ct"]), jnp.asarray(s["op_ss"]))
    read_vc = jnp.asarray(s["frontier"])
    want = reference_read(st, read_vc)
    got = pallas_kernels.orset_read_fused(
        st.dots, st.elem_slot, st.is_add, st.dot_dc, st.dot_seq,
        st.obs_vv, st.op_dc, st.op_ct, st.op_ss, st.valid2d,
        st.base_vc, st.has_base, read_vc,
        block_k=64, interpret=True)
    assert (np.asarray(got) == want).all()


def _filled_store(seed=4, K=192, B=384, D=8, n_dcs=3, gc_at=1, rounds=4):
    rng = np.random.default_rng(seed)
    clock = np.zeros(n_dcs, dtype=np.int32)
    st = store.orset_shard_init(K, n_lanes=8, n_slots=8, n_dcs=D,
                                dtype=jnp.int32)
    for i in range(rounds):
        s = orset_batch(rng, K, B, D, n_dcs, clock, obs_lag=2)
        lane = jnp.asarray(store.batch_lane_offsets(s["key_idx"]))
        st, _ = store.orset_append(
            st, jnp.asarray(s["key_idx"]), lane,
            jnp.asarray(s["elem_slot"]), jnp.asarray(s["is_add"]),
            jnp.asarray(s["dot_dc"]), jnp.asarray(s["dot_seq"]),
            jnp.asarray(s["obs_vv"]), jnp.asarray(s["op_dc"]),
            jnp.asarray(s["op_ct"]), jnp.asarray(s["op_ss"]))
        if i == gc_at:
            st = store.orset_gc(st, jnp.asarray(s["frontier"]))
    return st, jnp.asarray(s["frontier"])


@pytest.mark.parametrize("block_k", [64, 192])
def test_store_integrated_fused_read(block_k):
    """store.orset_read_full(fused=True) — the call the bench and any
    bulk reader uses — matches the jnp reference path."""
    st, read_vc = _filled_store()
    want = reference_read(st, read_vc)
    got = store.orset_read_full(st, read_vc, fused=True, block_k=block_k,
                                interpret=True)
    assert (np.asarray(got) == want).all()


def test_fused_read_non_divisible_block():
    """K not a multiple of block_k: the padded tail block's garbage is
    dropped on the bounds-masked write (pins the padding contract)."""
    st, read_vc = _filled_store(seed=11, K=200, B=256)
    want = reference_read(st, read_vc)
    got = store.orset_read_full(st, read_vc, fused=True, block_k=64,
                                interpret=True)
    assert np.asarray(got).shape == want.shape
    assert (np.asarray(got) == want).all()


def test_auto_falls_back_for_int64_shards():
    """µs-int64 live shards must take the jnp path (int32 pallas math
    would truncate timestamps)."""
    st, read_vc = _filled_store(seed=2, K=64, B=128)
    st64 = _int64_twin(st)
    want = reference_read(st64, read_vc.astype(jnp.int64))
    got = store.orset_read_full(st64, read_vc.astype(jnp.int64))
    assert (np.asarray(got) == want).all()


def test_with_base_snapshot_and_gc():
    K, B, D, n_dcs = 128, 256, 8, 3
    rng = np.random.default_rng(9)
    clock = np.zeros(n_dcs, dtype=np.int32)
    st = store.orset_shard_init(K, n_lanes=8, n_slots=8, n_dcs=D,
                                dtype=jnp.int32)
    for i in range(4):
        s = orset_batch(rng, K, B, D, n_dcs, clock, obs_lag=1)
        lane = jnp.asarray(store.batch_lane_offsets(s["key_idx"]))
        st, _ = store.orset_append(
            st, jnp.asarray(s["key_idx"]), lane,
            jnp.asarray(s["elem_slot"]), jnp.asarray(s["is_add"]),
            jnp.asarray(s["dot_dc"]), jnp.asarray(s["dot_seq"]),
            jnp.asarray(s["obs_vv"]), jnp.asarray(s["op_dc"]),
            jnp.asarray(s["op_ct"]), jnp.asarray(s["op_ss"]))
        if i == 1:  # fold a base snapshot so has_base/covered paths run
            st = store.orset_gc(st, jnp.asarray(s["frontier"]))
    read_vc = jnp.asarray(s["frontier"])
    want = reference_read(st, read_vc)
    got = pallas_kernels.orset_read_fused(
        st.dots, st.elem_slot, st.is_add, st.dot_dc, st.dot_seq,
        st.obs_vv, st.op_dc, st.op_ct, st.op_ss, st.valid2d,
        st.base_vc, st.has_base, read_vc,
        block_k=32, interpret=True)
    assert (np.asarray(got) == want).all()


@pytest.mark.parametrize("block_k", [64, 192])
def test_hybrid_read_matches_jnp_path(block_k):
    """fused="hybrid" (XLA inclusion mask + Pallas fold) must equal the
    reference path."""
    st, read_vc = _filled_store(seed=6)
    want = reference_read(st, read_vc)
    got = store.orset_read_full(st, read_vc, fused="hybrid",
                                block_k=block_k, interpret=True)
    assert (np.asarray(got) == want).all()


@pytest.mark.parametrize("seed", range(3))
def test_gc_matches_jnp_path(seed):
    """orset_gc_full(fused=True) — the fused GC fold — produces the
    exact dots/valid/base the jnp orset_gc produces, including on a
    store that already has a folded base and live unstable lanes."""
    st, frontier = _filled_store(seed=seed + 10)
    # a GST strictly between base and frontier: some lanes fold, some
    # survive (the interesting mixed case)
    gst = (np.asarray(frontier) // 2).astype(np.int32)
    got = store.orset_gc_full(st, jnp.asarray(gst), fused=True,
                              block_k=64, interpret=True)
    st2, _ = _filled_store(seed=seed + 10)  # orset_gc donates its input
    want = store.orset_gc(st2, jnp.asarray(gst))
    assert (np.asarray(got.dots) == np.asarray(want.dots)).all()
    assert (np.asarray(got.valid) == np.asarray(want.valid)).all()
    assert (np.asarray(got.base_vc) == np.asarray(want.base_vc)).all()
    assert bool(got.has_base) == bool(want.has_base)


def test_gc_full_reads_agree_after_fold():
    """A read after the fused GC equals a read after the jnp GC (the
    fold is transparent to materialization)."""
    st, frontier = _filled_store(seed=21)
    gst = (np.asarray(frontier) // 2).astype(np.int32)
    b = store.orset_gc_full(st, jnp.asarray(gst), fused=True, block_k=64,
                            interpret=True)
    st2, _ = _filled_store(seed=21)      # orset_gc donates its input
    a = store.orset_gc(st2, jnp.asarray(gst))
    ra = reference_read(a, frontier)
    rb = reference_read(b, frontier)
    assert (ra == rb).all()


def _int64_twin(st):
    return store.OrsetShardState(
        dots=st.dots.astype(jnp.int64), base_vc=st.base_vc.astype(jnp.int64),
        has_base=st.has_base, ops=st.ops.astype(jnp.int64),
        valid=st.valid, n_lanes=st.n_lanes)


@pytest.mark.parametrize("fused", [True, "hybrid"])
def test_explicit_fused_on_int64_shard_raises(fused):
    """An explicit fused request on a µs-int64 shard cannot be honoured
    (the kernels compute in int32): it raises, it does not quietly
    return the jnp answer."""
    st, read_vc = _filled_store(seed=2, K=64, B=128)
    st64 = _int64_twin(st)
    with pytest.raises(ValueError, match="int32"):
        store.orset_read_full(st64, read_vc.astype(jnp.int64),
                              fused=fused, interpret=True)
    if fused is True:
        with pytest.raises(ValueError, match="int32"):
            store.orset_gc_full(st64, read_vc.astype(jnp.int64),
                                fused=True, interpret=True)


def test_fused_without_interpret_is_not_interpreted_off_tpu():
    """Off a TPU an explicit fused=True without interpret=True must
    fail in the lowering: store.py never picks interpret mode itself."""
    st, read_vc = _filled_store(seed=2, K=64, B=128)
    with pytest.raises(Exception, match="(?i)interpret|tpu|mosaic"):
        np.asarray(store.orset_read_full(st, read_vc, fused=True,
                                         block_k=64))


def test_probe_records_the_block_that_compiled():
    """No block_k given: the ladder's choice is visible in
    store.BLOCK_K_CHOSEN (and logged), not hidden in the call."""
    st, read_vc = _filled_store(seed=5, K=64, B=128)
    store.BLOCK_K_CHOSEN.clear()
    got = store.orset_read_full(st, read_vc, fused=True, interpret=True)
    assert (np.asarray(got) == reference_read(st, read_vc)).all()
    assert list(store.BLOCK_K_CHOSEN.values()) == [256]
