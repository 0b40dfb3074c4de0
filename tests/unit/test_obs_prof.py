"""Device-plane profiler (ISSUE 2, antidote_tpu/obs/prof.py): the
kernel-span layer's no-device/no-op discipline, compile-cache-miss
attribution, txn-tree kernel child-spans, the /debug/prof endpoint,
and the /healthz ring-occupancy fields.  (The tracing.py shim was
retired to a one-release import error in ISSUE 7 —
tests/unit/test_tracing.py pins that.)"""

import json
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from antidote_tpu import stats
from antidote_tpu.obs import prof
from antidote_tpu.obs.events import FlightRecorder, recorder
from antidote_tpu.obs.prof import kernel_span, profiler
from antidote_tpu.obs.spans import tracer


@pytest.fixture(autouse=True)
def _isolate_obs_globals(tmp_path):
    """tracer/recorder/profiler are process-global; snapshot the knobs
    and clear aggregates so tests neither leak into nor inherit from
    each other (the test_obs.py discipline)."""
    saved = (tracer.sample_rate, recorder.dump_dir, profiler.enabled)
    tracer.clear()
    recorder.clear()
    profiler.reset()
    recorder.dump_dir = str(tmp_path / "flightrec")
    yield
    (tracer.sample_rate, recorder.dump_dir, enabled) = saved
    profiler.configure(enabled=enabled)
    tracer.clear()
    recorder.clear()
    profiler.reset()


# --------------------------------------------------------- no-op discipline


def test_disabled_hooks_are_cheap_noops():
    """Satellite contract: with profiling disabled every hook is a
    passthrough — zero new jit compile-cache entries, no recorded
    stats, bounded wall overhead (JAX_PLATFORMS=cpu in tier-1)."""

    @jax.jit
    def toy_kernel(x):
        return x * 2 + 1

    wrapped = profiler.wrap(toy_kernel, name="toy_noop", subsystem="t")
    x = jnp.arange(64)
    np.asarray(wrapped(x))          # compile once while enabled
    profiler.configure(enabled=False)
    cache_before = toy_kernel._cache_size()
    calls_before = profiler.snapshot()["kernels"]["toy_noop"]["calls"]

    n = 200
    t0 = time.perf_counter()
    for _ in range(n):
        wrapped(x)
    dt_wrapped = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(n):
        toy_kernel(x)
    dt_raw = time.perf_counter() - t0

    # zero new compile-cache entries from the disabled hooks
    assert toy_kernel._cache_size() == cache_before
    # nothing recorded while disabled
    snap = profiler.snapshot()["kernels"]["toy_noop"]
    assert snap["calls"] == calls_before
    # bounded overhead: generous bound (3x + absolute slack) so a noisy
    # CI core cannot flake this, while a tree-flatten-per-call
    # regression (~10x) still fails
    assert dt_wrapped < dt_raw * 3 + 0.05, (dt_wrapped, dt_raw)
    # and no spans leaked from the disabled path
    assert not tracer.spans(cat="kernel")


def test_wrapper_preserves_name_and_semantics():
    @kernel_span("t")
    @jax.jit
    def add_one(x):
        return x + 1

    assert add_one.__name__ == "add_one"
    assert add_one.__kernel_span__ == ("add_one", "t")
    assert int(add_one(jnp.asarray(41))) == 42


def test_wrapper_passes_through_inside_jit_traces():
    """A wrapped kernel composed into an outer jit must not record
    per-trace stats (timing a trace measures compilation)."""

    @kernel_span("t", name="inner_composed")
    @jax.jit
    def inner(x):
        return x + 1

    @jax.jit
    def outer(x):
        return inner(x) * 2

    np.asarray(outer(jnp.arange(4)))
    kernels = profiler.snapshot()["kernels"]
    assert "inner_composed" not in kernels


# ----------------------------------------------------- compile-miss counters


def test_compile_cache_miss_counting_by_shape():
    @kernel_span("t", name="miss_probe")
    @jax.jit
    def k(x):
        return x.sum()

    k(jnp.arange(8))
    k(jnp.arange(8))                        # same shape: no new miss
    k(jnp.arange(16))                       # new shape: miss
    snap = profiler.snapshot()["kernels"]["miss_probe"]
    assert snap["calls"] == 3
    assert snap["compile_misses"] == 2
    assert stats.registry.kernel_compile_misses.value(
        kernel="miss_probe") == 2
    assert stats.registry.kernel_calls.value(
        kernel="miss_probe", subsystem="t") == 3


def test_same_name_distinct_programs_each_count_a_miss():
    """fused_read / _sm mint several jit objects under ONE kernel
    name; a same-shape first call of a DIFFERENT program is still a
    fresh XLA compile and must count."""

    def make(mul):
        @jax.jit
        def body(x, _m=mul):
            return x * _m
        return profiler.wrap(body, name="shared_name_probe",
                             subsystem="t")

    a, b = make(2), make(3)
    x = jnp.arange(4)
    a(x)
    b(x)                                    # same shapes, new program
    assert profiler.snapshot()["kernels"]["shared_name_probe"][
        "compile_misses"] == 2


def test_static_scalar_args_mint_distinct_signatures():
    @kernel_span("t", name="static_probe")
    @jax.jit
    def k(x, n: int):
        return x * n

    k(jnp.arange(4), 2)
    k(jnp.arange(4), 3)                     # new static value: new sig
    assert profiler.snapshot()["kernels"]["static_probe"][
        "compile_misses"] == 2


# ----------------------------------------------------------- kernel spans


def test_kernel_child_span_joins_sampled_txn_tree():
    tracer.sample_rate = 1.0

    @kernel_span("mat.store", name="span_probe")
    @jax.jit
    def k(x):
        return x + 1

    with tracer.span("device_read", "device", txid="ktx1"):
        k(jnp.arange(4))
    roots = tracer.tree("ktx1")
    assert len(roots) == 1
    children = [c["span"].name for c in roots[0]["children"]]
    assert "kernel:span_probe" in children
    (kspan,) = tracer.spans(name="kernel:span_probe")
    assert kspan.cat == "kernel" and kspan.txid == "ktx1"
    assert kspan.args["subsystem"] == "mat.store"
    # the span is the host's dispatch and says so: nothing waits for
    # the device in order to time it (PR 25)
    assert kspan.args["timing"] == "dispatch"
    assert "complete" not in kspan.args
    assert "kernel" in tracer.planes("ktx1")


def test_unsampled_calls_record_no_spans():
    tracer.sample_rate = 0.0

    @kernel_span("t", name="quiet_probe")
    @jax.jit
    def k(x):
        return x + 1

    with tracer.span("device_read", "device", txid="qx"):
        k(jnp.arange(4))
    assert not tracer.spans(cat="kernel")
    # ...but the aggregate counters still advanced (always-on profile)
    assert profiler.snapshot()["kernels"]["quiet_probe"]["calls"] == 1


def test_buffer_hwm_gauge_tracks_output_bytes():
    @kernel_span("hwm_sub", name="hwm_probe")
    @jax.jit
    def k(x):
        return x * 2

    k(jnp.zeros(16, jnp.int64))
    k(jnp.zeros(1024, jnp.int64))
    k(jnp.zeros(8, jnp.int64))              # smaller: hwm unchanged
    snap = profiler.snapshot()
    assert snap["subsystem_bytes_hwm"]["hwm_sub"] == 1024 * 8
    assert stats.registry.device_buffer_hwm.value(
        subsystem="hwm_sub") == 1024 * 8


# ------------------------------------------------------- capture unification


def test_capture_window_annotates_wrapped_kernels(tmp_path):
    @kernel_span("t", name="cap_probe")
    @jax.jit
    def k(x):
        return x.sum()

    with prof.profile(str(tmp_path)):
        assert prof.active_dir() == str(tmp_path)
        np.asarray(k(jnp.arange(128.0)))
    assert prof.active_dir() is None
    snap = profiler.snapshot()["kernels"]["cap_probe"]
    # the capture forced no completion fetch: the call is counted and
    # nothing about completion is kept (PR 25)
    assert snap["calls"] == 1
    assert "completions" not in snap and "complete_mean_s" not in snap


# ------------------------------------------------------------- device plane


def test_device_workload_profiles_kernels_end_to_end(tmp_path):
    """Acceptance: after a device-plane workload /debug/prof shows
    per-kernel timing + compile-miss counts, and a sampled txn's span
    tree holds at least one kernel child-span."""
    from antidote_tpu.api import AntidoteTPU
    from antidote_tpu.config import Config

    tracer.sample_rate = 1.0
    cfg = Config(trace_sample_rate=1.0, device_async_flush=False)
    db = AntidoteTPU(dc_id="dcp", config=cfg,
                     data_dir=str(tmp_path / "data"))
    try:
        # 6 increments (under the 8-lane ring: no overflow/evict); the
        # coordinator's commit-warmed value cache would serve a
        # latest-snapshot read, so the profiled read uses a snapshot
        # taken BEFORE one more commit — frontier > snapshot bypasses
        # the cache and runs the batched device fold
        for _ in range(6):
            tx = db.start_transaction()
            db.update_objects(
                [(("prof_k", "counter_pn"), "increment", 1)], tx)
            db.commit_transaction(tx)
        tx_r = db.start_transaction()
        tx_w = db.start_transaction()
        db.update_objects(
            [(("prof_k", "counter_pn"), "increment", 1)], tx_w)
        db.commit_transaction(tx_w)
        (val,) = db.read_objects([("prof_k", "counter_pn")], tx_r)
        db.commit_transaction(tx_r)
        assert val == 6
        kspans = tracer.spans(cat="kernel")
        assert kspans, "device workload recorded no kernel spans"
        assert any(s.txid == tx_r.txid for s in kspans), \
            "no kernel span joined the sampled txn's tree"
        snap = profiler.snapshot()
        fold = snap["kernels"].get("counter_read_keys")
        assert fold is not None, snap["kernels"].keys()
        assert fold["calls"] >= 1 and fold["compile_misses"] >= 1
        assert fold["dispatch_total_s"] > 0
        assert "completions" not in fold  # no completion timing
        assert "detail" not in snap
    finally:
        db.close()


# --------------------------------------------------------------- endpoints


def test_debug_prof_endpoint_serves_snapshot():
    @kernel_span("t", name="http_probe")
    @jax.jit
    def k(x):
        return x + 1

    k(jnp.arange(4))
    srv = stats.MetricsServer(port=0, reg=stats.Registry()).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        doc = json.load(urllib.request.urlopen(
            base + "/debug/prof", timeout=5))
        assert doc["enabled"] is True
        k0 = doc["kernels"]["http_probe"]
        assert k0["calls"] >= 1 and k0["compile_misses"] >= 1
        # jax is live in-process, so the census must resolve
        assert doc["live_buffers"] and doc["live_buffers"]["count"] > 0
        # KERNEL_* families ride the exposition beside the new route
        body = urllib.request.urlopen(
            base + "/metrics", timeout=5).read().decode()
        for name in ("antidote_kernel_dispatch_latency_seconds",
                     "antidote_kernel_calls_total",
                     "antidote_kernel_compile_cache_misses_total"):
            assert name in body, name
        # the completion histogram went with the fetch that fed it
        assert "antidote_kernel_complete_latency_seconds" not in body
    finally:
        srv.stop()


def test_healthz_reports_ring_occupancy():
    tracer.sample_rate = 1.0
    with tracer.span("txn_commit", "coordinator", txid="hz1"):
        pass
    srv = stats.MetricsServer(port=0, reg=stats.Registry()).start()
    try:
        health = json.load(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/healthz", timeout=5))
        assert health["span_ring_capacity"] == tracer.capacity
        assert 0.0 < health["span_ring_fill_pct"] <= 100.0
        assert health["flight_recorder_dropped"] == {}
        assert health["flight_recorder_dropped_total"] == 0
    finally:
        srv.stop()


def test_flight_recorder_counts_ring_drops():
    rec = FlightRecorder(capacity=4)
    for i in range(10):
        rec.record("flood", "e", i=i)
    rec.record("calm", "e")
    assert rec.drop_counts() == {"flood": 6}
    assert rec.ring_fill()["flood"] == 1.0
    assert rec.ring_fill()["calm"] == pytest.approx(0.25)
    rec.clear()
    assert rec.drop_counts() == {}
