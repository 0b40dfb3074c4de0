"""Shared bench plumbing: platform flags, completion-barrier timing,
JSON output.

JAX dispatch is asynchronous: a call returns before the device has run
it, so a host clock around single calls measures the enqueue.  The
clock here ends device work with a small device->host fetch of the
result (which cannot complete before the work has), minus the fetch's
own round-trip overhead.  ``timed`` implements that — as one dependent
chain of calls in ``thread=True`` mode (one end fetch), or as
fetch-per-call otherwise.  (Replacing this clock with
``block_until_ready`` and a profiler trace is ROADMAP 1.1.)
"""

from __future__ import annotations

import json
import sys
import time


def setup(argv=None):
    """Apply --cpu / --quick flags; returns (quick, jax).  ``--cpu`` is
    the explicit logic-check mode.  Without it a bench needs the chip
    and exits non-zero when JAX finds none: a CPU number must never be
    printed under a device metric's name."""
    argv = sys.argv if argv is None else argv
    import jax

    from antidote_tpu.runtime import enable_compile_cache, tune_runtime

    if "--cpu" in argv:
        jax.config.update("jax_platforms", "cpu")
    elif jax.default_backend() != "tpu":
        sys.exit(f"bench: no TPU (JAX's backend is "
                 f"{jax.default_backend()!r}); pass --cpu for a logic "
                 "check")
    else:
        # a config run again loads its programs instead of compiling
        enable_compile_cache()
    # benches measure the SERVING configuration (GC + GIL knobs a node
    # process applies at startup), not the default interpreter
    tune_runtime()
    return "--quick" in argv, jax


def fetch(x):
    """Force completion: device->host transfer of one scalar of x."""
    import jax
    import numpy as np

    leaf = jax.tree_util.tree_leaves(x)[0]
    idx = tuple(0 for _ in leaf.shape)
    return np.asarray(leaf[idx] if leaf.shape else leaf)


def timed(fn, *args, iters=3, warmup=1, block=None, thread=False):
    """Seconds per call of ``fn(*args)``.

    Warmup calls absorb compilation; then each timed call is forced to
    completion by a scalar device->host fetch on ``block(result)``
    (default: the result itself) — the completion barrier (see module
    doc).  The fetch's own round-trip is measured separately and
    subtracted per call.

    ``thread=True`` runs ``state = fn(state)`` chains (first arg is the
    initial state) — required when fn donates its input buffers, and the
    natural shape for steady-state store throughput.
    """
    block = block if block is not None else (lambda r: r)

    def probe_fetch_oh(r):
        # min of several probes: one spiked round-trip sample must not be
        # amplified by the per-call subtraction below
        samples = []
        for _ in range(3):
            t0 = time.perf_counter()
            fetch(block(r))
            samples.append(time.perf_counter() - t0)
        return min(samples)

    if thread:
        (state,) = args
        for _ in range(max(warmup, 1)):
            state = fn(state)
        fetch(block(state))
        fetch_oh = probe_fetch_oh(state)
        t0 = time.perf_counter()
        for _ in range(iters):
            state = fn(state)
        fetch(block(state))
        total = time.perf_counter() - t0
        return max(total - fetch_oh, 1e-9) / iters

    r = None
    for _ in range(max(warmup, 1)):
        r = fn(*args)
    fetch(block(r))
    fetch_oh = probe_fetch_oh(r)

    t0 = time.perf_counter()
    for _ in range(iters):
        r = fn(*args)
        fetch(block(r))
    total = time.perf_counter() - t0
    return max(total - iters * fetch_oh, 1e-9) / iters


def emit(metric, value, unit, vs_baseline, **detail):
    print(json.dumps({
        "metric": metric, "value": value, "unit": unit,
        "vs_baseline": vs_baseline, "detail": detail,
    }))
