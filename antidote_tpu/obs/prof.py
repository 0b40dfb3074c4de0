"""Device-plane profiler — the kernel-span layer over the jitted hot
paths, plus the XProf capture API (absorbed from the old
antidote_tpu.tracing module so the process has ONE tracing namespace;
that shim is retired to a one-release import error, ISSUE 7).

PR 1 made the *host* planes observable (txid spans, flight recorder,
stage histograms); the fused XLA/Pallas programs in antidote_tpu/mat/
stayed a black box.  This module closes that gap in the Dapper spirit
of always-on, sampled production profiling:

- **Kernel spans** — every jitted entry point of the materializer,
  sharded store, and dependency gate is wrapped (``@kernel_span`` at
  the definition, or :meth:`DeviceProfiler.wrap` around dynamically
  built jits).  Each call records dispatch wall time — the host's
  time to enqueue the program, never the device's time to run it —
  and, when it runs under a recorded span (obs/spans.py) or an open
  capture, a ``kernel:*`` child-span of that dispatch time joins the
  request's trace tree.  The host never waits for the device in order
  to time it: a kernel's device time is the profiler's (the device
  plane of a capture, by program name).
- **Compile-cache-miss counters** — keyed by function + abstract shape
  signature (shapes/dtypes of array leaves, values of static scalars),
  so a recompilation storm is attributable to the kernel and shape
  that minted it instead of showing up as an anonymous p99 spike.
- **Device-buffer census** — per-subsystem high-watermark gauges over
  the LARGEST single state pytree any of the subsystem's kernels has
  returned (a lower bound on its footprint — several plane states
  co-reside; the global ``jax.live_arrays()`` census in
  :meth:`DeviceProfiler.snapshot`, served by stats.py's
  ``/debug/prof``, is the total).
- **Capture unification** — :func:`start` is the one door to a
  profiler capture.  While it is open the span tracer records every
  span and holds a ``jax.profiler.TraceAnnotation`` around each
  thread's innermost work span (obs/spans.py), wrapped kernel calls
  among them, so the ``.xplane.pb`` names the program's stages beside
  the device plane and on its clock.  :func:`stop` keeps the spans of
  the capture; :func:`last_capture` reduces them to one summary
  (per-name totals and self times, per-kind request totals,
  ``host_busy_s``) and adds, under ``host``, what the process did
  between the capture's bounds (``obs.host.difference``: CPU, the
  partition locks' holds, the collector's pauses).

Cost discipline: with ``profiler.enabled`` False every hook is a single
attribute check + passthrough (no tree flattening, no jnp ops, zero
new compile-cache entries — tests/unit/test_obs_prof.py pins this).
Enabled (the default), the per-call cost is a few µs of host
bookkeeping on *batch-level* dispatches.  Calls made
while a jit trace is being staged (a wrapped store fn composed into
fused_read / shard_map bodies) pass straight through — timing a trace
would record compilation, not execution.
"""

from __future__ import annotations

import contextlib
import functools
import threading
import time
from typing import Any, Dict, Optional

from antidote_tpu.obs import host
from antidote_tpu.obs.spans import summarize, tracer

# ------------------------------------------------------------------ capture
# (one capture at a time, mirroring jax.profiler's own constraint)

_capture_lock = threading.Lock()
_active_dir: Optional[str] = None
#: the last capture's spans as Tracer.capture_end left them, and their
#: summary once last_capture() has been asked for it
_last_raw: Optional[Dict[str, Any]] = None
_last_summary: Optional[Dict[str, Any]] = None
#: obs.host.account() as the open capture began; the last capture's
#: difference
_host_from: Optional[Dict[str, Any]] = None
_last_host: Optional[Dict[str, Any]] = None


def annotate(name: str):
    """Context manager labeling the enclosed host+device work in a
    profiler capture; no-op cost when no capture is active."""
    import jax

    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def profile(log_dir: str):
    """Capture a JAX profiler trace of the enclosed block into
    ``log_dir`` (inspect with TensorBoard's profile plugin / XProf)."""
    start(log_dir)
    try:
        yield log_dir
    finally:
        stop()


def start(log_dir: str) -> None:
    """Begin a capture (idempotent per process: one capture at a time).
    While the window is open the span tracer records every span and
    annotates the profiler's timeline with the work spans' names."""
    global _active_dir, _host_from
    import jax

    with _capture_lock:
        if _active_dir is not None:
            raise RuntimeError(
                f"profiler already capturing to {_active_dir}")
        jax.profiler.start_trace(log_dir)
        _active_dir = log_dir
        _host_from = host.account()
        tracer.capture_begin(jax.profiler.TraceAnnotation)


def stop() -> str:
    """End the capture; returns the trace directory.  The spans
    recorded since :func:`start` are kept for :func:`last_capture`."""
    global _active_dir, _last_raw, _last_summary, _last_host
    import jax

    with _capture_lock:
        if _active_dir is None:
            raise RuntimeError("no profiler capture active")
        # the spans and the accounts first: writing the trace out takes
        # seconds, which are not the capture's
        _last_raw, _last_summary = tracer.capture_end(), None
        _last_host = host.difference(_host_from, host.account())
        jax.profiler.stop_trace()
        out, _active_dir = _active_dir, None
        return out


def active_dir() -> Optional[str]:
    return _active_dir


def last_capture() -> Optional[Dict[str, Any]]:
    """The summary of the last finished capture's spans
    (``spans.summarize``) with the host's account of it under
    ``host``, or None when this process has finished none.  Reduced on
    first demand, not inside :func:`stop`, which runs while the
    capture's traffic is still being served."""
    global _last_summary
    with _capture_lock:
        if _last_summary is None and _last_raw is not None:
            _last_summary = summarize(**_last_raw)
            _last_summary["host"] = _last_host
        return _last_summary


# --------------------------------------------------------- kernel-span layer


def _sig(leaves: list) -> tuple:
    """Abstract-shape signature of a call's flattened arguments:
    (shape, dtype) per array leaf, the value itself for Python scalars.
    Value-keying scalars is right for THIS codebase's wrapped kernels,
    where a raw Python scalar only ever reaches a jit as a static arg
    (pallas block_k / interpret, rga_merge actor_bits — distinct
    values mint distinct programs); a kernel taking a *traced* Python
    scalar would have its misses overcounted, which the per-kernel
    signature cap below bounds."""
    out = []
    for x in leaves:
        if x is None or isinstance(x, (bool, int, float, str)):
            out.append(("static", x))
        else:
            out.append((tuple(getattr(x, "shape", ())),
                        str(getattr(x, "dtype", ""))))
    return tuple(out)


def _nbytes(out) -> int:
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(out):
        nb = getattr(leaf, "nbytes", None)
        if nb is not None:
            total += int(nb)
    return total


#: per-kernel signature-set bound: past this the set clears en masse
#: (the spans decision-cache idiom) — the miss counter may then
#: recount old shapes, but a long-running node cannot grow host memory
#: without bound when a kernel's signature space is large
_SHAPES_CAP = 1024


class _KernelStat:
    """Aggregate for one wrapped kernel (mutated under the profiler
    lock; snapshot() copies the scalars out)."""

    __slots__ = ("subsystem", "calls", "dispatch_s", "compile_misses",
                 "shapes", "bytes_out_hwm", "last_call_us")

    def __init__(self, subsystem: str):
        self.subsystem = subsystem
        self.calls = 0
        self.dispatch_s = 0.0
        self.compile_misses = 0
        self.shapes: set = set()
        self.bytes_out_hwm = 0
        self.last_call_us = 0


class DeviceProfiler:
    """Process-global kernel profiler (all DCs share it, like
    stats.registry and obs.spans.tracer)."""

    def __init__(self):
        #: master switch — False makes every wrapped call a bare
        #: passthrough (Config.kernel_profile via obs.configure)
        self.enabled = True
        self._stats: Dict[str, _KernelStat] = {}
        self._subsys_hwm: Dict[str, int] = {}
        self._lock = threading.Lock()

    # -------------------------------------------------------- configuration

    def configure(self, enabled: Optional[bool] = None) -> None:
        if enabled is not None:
            self.enabled = bool(enabled)

    def reset(self) -> None:
        """Drop all aggregates (test isolation)."""
        with self._lock:
            self._stats.clear()
            self._subsys_hwm.clear()

    # ------------------------------------------------------------- wrapping

    def wrap(self, fn, name: Optional[str] = None,
             subsystem: str = "mat"):
        """Wrap a jitted callable in the kernel-span layer.  Semantics
        are preserved exactly (args pass through, donation and
        exceptions included); ``__name__`` is kept so callers that key
        caches on it (device_plane._FUSED_CACHE) see no change."""
        kname = name or getattr(fn, "__name__", repr(fn))

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if not self.enabled:
                return fn(*args, **kwargs)
            return self._call(fn, kname, subsystem, args, kwargs)

        wrapper.__kernel_span__ = (kname, subsystem)
        return wrapper

    def _stat(self, kname: str, subsystem: str) -> _KernelStat:
        st = self._stats.get(kname)
        if st is None:
            with self._lock:
                st = self._stats.setdefault(kname, _KernelStat(subsystem))
        return st

    def _call(self, fn, kname: str, subsystem: str, args, kwargs):
        import jax

        from antidote_tpu import stats as _stats

        leaves = jax.tree_util.tree_leaves((args, kwargs))
        if any(isinstance(x, jax.core.Tracer) for x in leaves):
            # composed into an outer jit / shard_map body (fused_read,
            # the sharded stores' locals): the call stages a trace, so
            # there is no execution to time and no value to fetch
            return fn(*args, **kwargs)
        reg = _stats.registry
        st = self._stat(kname, subsystem)
        # the underlying jit object's id joins the key: several distinct
        # programs can share one kernel NAME (fused_read's per-pattern
        # jits, _sm's per-instance shard_maps), and same-shape calls of
        # a DIFFERENT program are still fresh XLA compiles (id reuse
        # after a dropped jit is GC'd can undercount — acceptable for a
        # storm detector)
        sig = (id(fn),) + _sig(leaves)
        if sig not in st.shapes:
            # first call at a new abstract shape = a jit compile-cache
            # miss for this kernel (jax specializes per shape); counting
            # here attributes a recompilation storm to its source
            with self._lock:
                if sig not in st.shapes:
                    if len(st.shapes) >= _SHAPES_CAP:
                        st.shapes.clear()
                    st.shapes.add(sig)
                    st.compile_misses += 1
                    reg.kernel_compile_misses.inc(kernel=kname)
        t0_us = time.time_ns() // 1000
        t0 = time.perf_counter()
        if tracer.current() is not None or tracer.capturing:
            # a child of the stage that dispatched it; its duration is
            # the host's dispatch, as ``timing`` says
            with tracer.span(f"kernel:{kname}", "kernel",
                             subsystem=subsystem, timing="dispatch"):
                out = fn(*args, **kwargs)
        else:
            out = fn(*args, **kwargs)
        dispatch = time.perf_counter() - t0
        nb = _nbytes(out)
        with self._lock:
            st.calls += 1
            st.dispatch_s += dispatch
            st.last_call_us = t0_us
            if nb > st.bytes_out_hwm:
                st.bytes_out_hwm = nb
            if nb > self._subsys_hwm.get(subsystem, 0):
                self._subsys_hwm[subsystem] = nb
                reg.device_buffer_hwm.set(nb, subsystem=subsystem)
        reg.kernel_calls.inc(kernel=kname, subsystem=subsystem)
        reg.kernel_dispatch_latency.observe(dispatch)
        return out

    # -------------------------------------------------------------- queries

    def snapshot(self) -> Dict[str, Any]:
        """JSON-ready profiler state — the /debug/prof body."""
        with self._lock:
            kernels = {
                name: {
                    "subsystem": st.subsystem,
                    "calls": st.calls,
                    "compile_misses": st.compile_misses,
                    "dispatch_total_s": round(st.dispatch_s, 6),
                    "dispatch_mean_s": round(
                        st.dispatch_s / st.calls, 9) if st.calls else 0.0,
                    "bytes_out_hwm": st.bytes_out_hwm,
                    "last_call_us": st.last_call_us,
                }
                for name, st in self._stats.items()
            }
            subsys = dict(self._subsys_hwm)
        return {
            "enabled": self.enabled,
            "capture_dir": _active_dir,
            "kernels": kernels,
            "subsystem_bytes_hwm": subsys,
            "live_buffers": self._census(),
        }

    @staticmethod
    def _census() -> Optional[Dict[str, int]]:
        """Global live-device-buffer census.  Only runs when jax is
        already imported (never drags the runtime in from an endpoint)
        and degrades to None on any failure — a diagnostic read must
        not take the server down."""
        import sys

        jax = sys.modules.get("jax")
        if jax is None or not hasattr(jax, "live_arrays"):
            return None
        try:
            arrs = jax.live_arrays()
            return {"count": len(arrs),
                    "bytes": int(sum(int(getattr(a, "nbytes", 0) or 0)
                                     for a in arrs))}
        except Exception:  # noqa: BLE001 — census is best-effort
            return None


#: process-wide profiler (all DCs share it, like stats.registry)
profiler = DeviceProfiler()


def kernel_span(subsystem: str, name: Optional[str] = None):
    """Decorator marking a jitted entry point as a profiled kernel —
    the instrumentation idiom tools/trace_lint.py enforces on every
    public ``@jax.jit`` function under antidote_tpu/mat/::

        @kernel_span("mat.store")
        @partial(jax.jit, donate_argnums=(0,))
        def orset_append(...): ...
    """

    def deco(fn):
        return profiler.wrap(fn, name=name, subsystem=subsystem)

    return deco
