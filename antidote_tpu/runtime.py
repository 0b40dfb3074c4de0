"""Process-level runtime tuning for nodes that SERVE (the reference
ships BEAM flags for the same purpose: +C no_time_warp, scheduler
settings — reference config/vm.args:26-34).

Two CPython knobs dominate a serving process's tail and throughput:

- the CYCLIC GC: with the default (700, 10, 10) thresholds every ~700
  container allocations trigger a young-gen pass and, regularly, full
  sweeps of the whole live heap — which for a database node is large
  (materializer caches, device plane directories, logs).  Measured on
  the config6 update mix: 1243 -> 2707 txn/s from gc.freeze() +
  raised thresholds alone.  freeze() moves the already-built object
  graph out of every future scan; the raised thresholds keep young-gen
  passes off the per-transaction path.  The GC stays ENABLED: real
  cycles in new garbage still collect, just in much larger batches.

- the GIL switch interval: a serving thread woken by the fabric waits
  up to a full interval for a busy peer thread to yield; 5 ms default
  puts a multi-ms floor under every cross-thread handoff.
"""

from __future__ import annotations

import gc
import os
import sys

_tuned = False


def tune_runtime(switch_interval_s: float = 0.0005,
                 gc_thresholds=(50000, 50, 50)) -> None:
    """Idempotent per-process tuning; call when this process's main
    duty is serving a node (NodeServer does this automatically)."""
    global _tuned
    if _tuned:
        return
    _tuned = True
    sys.setswitchinterval(switch_interval_s)
    gc.freeze()
    gc.set_threshold(*gc_thresholds)


def shard_map_compat(fn, *, mesh, in_specs, out_specs, check_vma=True):
    """The one ``shard_map`` build site: every collective program is
    built here, which is the name tools/concurrency_lint.py's
    collective-lock rule follows to its launch sites."""
    import jax

    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX has
    already taken the directory from it and none is set here, so the
    cache can be placed from outside; otherwise it lives at
    ``<checkout>/.jax_cache`` — a fixed path, because the path is part
    of what a later process must find again.  A live node compiles one
    program per plane type x dispatch bucket x key capacity, most of
    them under JAX's default one-second caching floor, so the floor is
    dropped: a restarted node then loads its programs instead of
    compiling them.  Called before the first plane is built
    (Node.__init__), and by chip_smoke.py and the benches."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


#: process-wide serialization of XLA programs containing COLLECTIVES:
#: JAX's single-controller model does not support concurrent collective
#: programs over the same devices — two threads interleaving their
#: pmin/psum programs abort inside the XLA runtime (caught by the
#: causal-checker stress loops via the device stable fold).  Every
#: collective launch site takes this lock; real deployments run one
#: node per host process, so it is uncontended there.
import threading as _threading

COLLECTIVE_LOCK = _threading.Lock()
