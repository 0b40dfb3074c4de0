#!/usr/bin/env python
"""trace_lint — instrumentation-coverage check for the obs plane.

ISSUE 1 threads txid-correlated spans (antidote_tpu/obs/spans.py) and
profiler annotations (antidote_tpu/obs/prof.py; tracing.py is a shim)
through every public entry point of the coordinator, device plane,
log, and inter-DC planes.  Instrumentation rots silently: a refactor
that drops a ``with tracer.span(...)`` breaks no test, it just blinds
the next forensic hunt.  This lint pins the contract — every entry
point listed in ENTRY_POINTS must carry a span, an instant, a profiler
annotation, or the @traced decorator — and fails loudly when one goes
dark.

ISSUE 2 adds the device-kernel rule: every PUBLIC ``@jax.jit``-
decorated function under antidote_tpu/mat/ must also carry a
``@kernel_span`` (antidote_tpu/obs/prof.py) so per-kernel timing and
compile-cache-miss attribution cannot silently go dark when a new
jitted entry point lands.  ISSUE 3 extends the same rule to
antidote_tpu/interdc/ — the dependency gate's resident-ring kernels
(interdc/gate_kernels.py) are now a first-class device plane.

ISSUE 6 adds the publish rule: every function under
antidote_tpu/interdc/ that calls ``transport.publish`` / ``bus.publish``
(the pub/sub fabric's send) must carry a span or instant — the async
ship worker moved publishing off the commit path, and an untraced
publish site would make outbound frames invisible to the txid-
correlated forensic hunts the obs plane exists for.

ISSUE 7 adds the decode rule (the receive-side mirror of the publish
rule): every function under antidote_tpu/interdc/ or
antidote_tpu/cluster/ that DECODES a wire frame (``frame_from_bin`` /
``*.from_bin``) must record the arrival instant with a span/instant —
the visibility-latency SLOs subtract the carried origin-commit
wallclock from arrival-side time, so an untraced decode site is a
blind spot in every journey it feeds.  The decoder definitions
themselves (functions *named* frame_from_bin / from_bin) are exempt:
the rule binds call sites, where arrival happens.

ISSUE 8 adds the fused-read rule: every function under
antidote_tpu/mat/ that calls ``fused_read`` (the multi-fold one-
dispatch device program) must carry a span/instant — the read serve
plane moved these dispatches off the per-transaction call stack, and
an untraced gathered fold would make the hottest read-path kernel
invisible to the serve-stage latency panels and sampled txn trees.
The definition itself (a function *named* fused_read) is exempt; call
sites are not.

ISSUE 9 adds the sync rule: every function under antidote_tpu/oplog/
that calls ``sync()`` / ``fsync`` (/ the native ``oplog_sync``) must
carry a span or instant — the group-commit plane moved the fsync off
the partition lock and between threads, and an untraced durability
barrier would blind exactly the stall hunts the log_sync_wait /
log_group_drain timeline exists for.  Functions NAMED like the
barrier (``sync`` — the DurableLog/_PyLog definitions) are exempt;
call sites are not.

ISSUE 10 adds the checkpoint-IO rule: every function under
antidote_tpu/oplog/ that performs checkpoint IO — writing/loading the
checkpoint document (``write_doc`` / ``load_doc``) or truncating the
log (``truncate_below``) — must carry a span or instant.  These are
the cold-path disk moves recovery-time and retention forensics hinge
on (ckpt_write/ckpt_load spans, the log_truncate span, the CKPT_*
gauges), and they run from commit tails and remote bootstrap answers
— an untraced site would make a multi-second checkpoint stall
unattributable.  The IO definitions themselves (functions NAMED
write_doc / load_doc / truncate_below) are exempt; call sites are not.

Runs standalone (``python tools/trace_lint.py``) and from tier-1
(tests/unit/test_trace_lint.py); exit code 0 = fully instrumented.
Purely static (ast), so it needs no JAX and runs in milliseconds.
"""

from __future__ import annotations

import ast
import os
import sys
from typing import Dict, List

#: (relative module path) -> {class name: [method, ...]} — the public
#: entry points of each plane that MUST be instrumented.  Grow this
#: list when a PR adds a plane; never shrink it to silence the lint.
ENTRY_POINTS: Dict[str, Dict[str, List[str]]] = {
    # PR 25: the request path, wire to device.  PbServer.__init__
    # defines the handler class whose loop opens the pb_request root;
    # the static API calls replace the pb_static_read / static_read
    # instants; the manager's three waits and the serve plane's queue
    # have names; a device dispatch is prepare / dispatch / fetch
    "antidote_tpu/pb/server.py": {
        "PbServer": ["__init__"],
    },
    "antidote_tpu/api.py": {
        "AntidoteTPU": ["read_objects_static", "update_objects_static"],
    },
    "antidote_tpu/txn/coordinator.py": {
        "Coordinator": ["read_objects", "update_objects",
                        "commit_transaction", "abort_transaction",
                        "snapshot_for", "gr_snapshot_wait"],
    },
    "antidote_tpu/txn/manager.py": {
        "_TimedLock": ["__enter__"],
        "PartitionManager": ["_await_unprepared", "_wait_device_quiesce",
                             "checkpoint_now"],
    },
    "antidote_tpu/mat/serve.py": {
        "ReadServer": ["finish", "_lead_once", "_drain"],
    },
    "antidote_tpu/oplog/partition.py": {
        "PartitionLog": ["append_commit"],
    },
    "antidote_tpu/mat/device_plane.py": {
        "DevicePlane": ["stage", "read_many", "gc", "flush"],
        "_PlaneBase": ["_dispatch_rows", "_fetch_overflow",
                       "read_many_begin", "_many_reader", "flush", "gc"],
    },
    "antidote_tpu/mat/sharded.py": {
        "_ShardedBase": ["append", "read", "read_keys"],
    },
    "antidote_tpu/interdc/sender.py": {
        "InterDcLogSender": ["on_append"],
    },
    "antidote_tpu/interdc/dep.py": {
        "DependencyGate": ["_apply"],
    },
    "antidote_tpu/interdc/dc.py": {
        "DataCenter": ["_deliver"],
    },
}

#: a call to <obj>.<attr> counts as instrumentation when (obj, attr)
#: is one of these — the span/annotation surfaces of the obs plane
#: (the tracing.annotate shim form was retired with tracing.py,
#: ISSUE 7; prof.annotate is the home)
_INSTRUMENTED_CALLS = {
    ("tracer", "span"), ("tracer", "instant"),
    # PR 25: a span in which the thread sleeps, a request's root, and
    # a span that ends on another thread than it began on
    ("tracer", "wait_span"), ("tracer", "root"),
    ("tracer", "close_stamp"),
    ("prof", "annotate"),
}

#: packages whose public @jax.jit functions must carry @kernel_span
#: (ISSUE 2 for mat/, ISSUE 3 for interdc/ — the device-plane
#: profiler's coverage contract; grow this tuple when a new package
#: gains jitted entry points, never shrink it)
_KERNEL_SPAN_DIRS = (os.path.join("antidote_tpu", "mat"),
                     os.path.join("antidote_tpu", "interdc"))

#: decorators that wrap the whole method in a span
_INSTRUMENTED_DECORATORS = {"traced"}

#: attribute names that hold the inter-DC pub/sub fabric: a call
#: ``<something>.<one of these>.publish(...)`` (or a bare
#: ``transport.publish`` / ``bus.publish``) is a wire send and must be
#: instrumented (ISSUE 6); the package the rule sweeps
_PUBLISH_OWNERS = ("transport", "bus")
_PUBLISH_DIR = os.path.join("antidote_tpu", "interdc")

#: wire-frame decoder call names: a call to one of these (bare or as
#: an attribute — ``frame_from_bin(data)`` / ``InterDcTxn.from_bin(b)``)
#: marks the function as a frame-arrival site (ISSUE 7); the dirs the
#: rule sweeps
_DECODE_NAMES = ("frame_from_bin", "from_bin")
_DECODE_DIRS = (os.path.join("antidote_tpu", "interdc"),
                os.path.join("antidote_tpu", "cluster"))

#: gathered-fold call names: a call to one of these under mat/ (bare
#: or as an attribute) is a serve-side one-dispatch device fold and
#: must be instrumented (ISSUE 8); definitions are exempt like the
#: decode rule's
_FUSED_NAMES = ("fused_read",)
_FUSED_DIRS = (os.path.join("antidote_tpu", "mat"),)

#: durability-barrier call names under oplog/ (ISSUE 9): a call whose
#: terminal name is one of these is an fsync (or the flush+fsync
#: wrapper) and the calling function must be instrumented; functions
#: NAMED "sync" are the barrier definitions themselves and are exempt
_SYNC_NAMES = ("sync", "fsync", "oplog_sync")
_SYNC_DIR = os.path.join("antidote_tpu", "oplog")

#: checkpoint-IO call names under oplog/ (ISSUE 10): a call whose
#: terminal name is one of these moves checkpoint/retention state on
#: disk and the calling function must be instrumented; functions NAMED
#: like the IO primitives are the definitions themselves and exempt
_CKPT_IO_NAMES = ("write_doc", "load_doc", "truncate_below")
_CKPT_DIR = os.path.join("antidote_tpu", "oplog")


def _is_instrumented(fn: ast.FunctionDef) -> bool:
    for dec in fn.decorator_list:
        target = dec.func if isinstance(dec, ast.Call) else dec
        name = getattr(target, "attr", getattr(target, "id", None))
        if name in _INSTRUMENTED_DECORATORS:
            return True
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if (isinstance(f, ast.Attribute)
                and isinstance(f.value, ast.Name)
                and (f.value.id, f.attr) in _INSTRUMENTED_CALLS):
            return True
    return False


def _is_jax_jit(dec: ast.expr) -> bool:
    """True for ``@jax.jit``, ``@jit`` (from-imported), either with a
    call ``(...)``, and ``@[functools.]partial([jax.]jit, ...)``
    decorator forms.  The bare-name match can in principle catch a
    foreign ``jit`` (numba's), but under antidote_tpu/mat/ any jit is
    jax's — a false positive here is a lint nudge, not a build break."""
    if isinstance(dec, ast.Attribute):
        return (dec.attr == "jit" and isinstance(dec.value, ast.Name)
                and dec.value.id == "jax")
    if isinstance(dec, ast.Name):
        return dec.id == "jit"
    if isinstance(dec, ast.Call):
        f = dec.func
        name = getattr(f, "attr", getattr(f, "id", None))
        if name == "partial" and dec.args:
            return _is_jax_jit(dec.args[0])
        if name == "jit":
            return _is_jax_jit(f)
    return False


def _has_kernel_span(fn: ast.FunctionDef) -> bool:
    for dec in fn.decorator_list:
        target = dec.func if isinstance(dec, ast.Call) else dec
        if getattr(target, "attr",
                   getattr(target, "id", None)) == "kernel_span":
            return True
    return False


def _call_name(node: ast.expr):
    if isinstance(node, ast.Call):
        f = node.func
        return getattr(f, "attr", getattr(f, "id", None))
    return None


def _unwrapped_jit_assign(value: ast.expr) -> bool:
    """True when a module-level assignment VALUE is a bare jitted
    callable — ``jax.jit(f)`` / ``partial(jax.jit, ...)`` — with no
    kernel_span / profiler.wrap layer around it.  ISSUE 4 extends the
    lint here: the ingest plane's flush kernels are natural to land as
    ``flush = jax.jit(_impl)`` assignments, which the decorator-only
    rule never saw — an unprofiled flush kernel must not land either
    way.  ``kernel_span(...)(jax.jit(f))`` (store.py's
    _orset_gc_nodonate idiom, public form) and ``profiler.wrap(...)``
    both count as instrumented."""
    if not isinstance(value, ast.Call):
        return False
    if _is_jax_jit(value):
        return True
    name = _call_name(value)
    if name in ("kernel_span", "wrap"):
        return False  # instrumented wrapper
    # kernel_span("...")(jax.jit(f)): outer call whose func is a call
    if isinstance(value.func, ast.Call) \
            and _call_name(value.func) == "kernel_span":
        return False
    # partial(jax.jit, ...)(impl): the func itself is a jit factory
    if isinstance(value.func, ast.Call) and _is_jax_jit(value.func):
        return True
    # any other wrapper around a jit call still hides an unprofiled
    # kernel: look one level into the arguments
    return any(isinstance(a, ast.Call) and _is_jax_jit(a)
               for a in value.args)


def lint_kernel_spans(root: str) -> List[str]:
    """ISSUE 2/3 rule: public @jax.jit functions under the device-
    plane packages (mat/, interdc/) must carry @kernel_span so the
    profiler sees them.  ISSUE 4 extends the same contract to public
    module-level ``NAME = jax.jit(...)`` assignments (the ingest
    module's flush-kernel form)."""
    problems: List[str] = []
    for rel_dir in _KERNEL_SPAN_DIRS:
        d = os.path.join(root, rel_dir)
        if not os.path.isdir(d):
            continue
        for fname in sorted(os.listdir(d)):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(d, fname)
            with open(path) as f:
                tree = ast.parse(f.read(), filename=path)
            for node in tree.body:
                if isinstance(node, ast.FunctionDef):
                    if node.name.startswith("_"):
                        continue
                    if any(_is_jax_jit(dec)
                           for dec in node.decorator_list) \
                            and not _has_kernel_span(node):
                        problems.append(
                            f"{rel_dir}/{fname}::{node.name}: public "
                            "@jax.jit entry point without @kernel_span "
                            "— its timing and compile-miss attribution "
                            "are dark (antidote_tpu/obs/prof.py)")
                elif isinstance(node, (ast.Assign, ast.AnnAssign)):
                    targets = (node.targets
                               if isinstance(node, ast.Assign)
                               else [node.target])
                    names = [t.id for t in targets
                             if isinstance(t, ast.Name)]
                    if not names or all(n.startswith("_")
                                        for n in names):
                        continue
                    if node.value is not None \
                            and _unwrapped_jit_assign(node.value):
                        problems.append(
                            f"{rel_dir}/{fname}::{names[0]}: public "
                            "jitted assignment without kernel_span/"
                            "profiler.wrap — unprofiled flush kernels "
                            "cannot land (antidote_tpu/obs/prof.py)")
    return problems


def _is_publish_call(node: ast.Call) -> bool:
    """True for ``transport.publish(...)`` / ``self.bus.publish(...)``
    etc. — an Attribute call named ``publish`` whose owner is (or ends
    in an attribute named) one of _PUBLISH_OWNERS."""
    f = node.func
    if not isinstance(f, ast.Attribute) or f.attr != "publish":
        return False
    owner = f.value
    name = getattr(owner, "attr", getattr(owner, "id", None))
    return name in _PUBLISH_OWNERS


def lint_publish_spans(root: str) -> List[str]:
    """ISSUE 6 rule: every function under antidote_tpu/interdc/ with a
    ``transport.publish`` / ``bus.publish`` call site must also carry a
    span/instant/annotation, so outbound wire sends stay visible to the
    forensic plane even as they move between threads."""
    problems: List[str] = []
    d = os.path.join(root, _PUBLISH_DIR)
    if not os.path.isdir(d):
        return problems
    for fname in sorted(os.listdir(d)):
        if not fname.endswith(".py"):
            continue
        path = os.path.join(d, fname)
        with open(path) as f:
            tree = ast.parse(f.read(), filename=path)
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            has_publish = any(
                isinstance(c, ast.Call) and _is_publish_call(c)
                for c in ast.walk(node))
            if has_publish and not _is_instrumented(node):
                problems.append(
                    f"{_PUBLISH_DIR}/{fname}::{node.name}: "
                    "transport.publish call site without a tracer "
                    "span/instant — outbound frames go dark "
                    "(antidote_tpu/obs/spans.py)")
    return problems


def _is_decode_call(node: ast.Call) -> bool:
    """True for ``frame_from_bin(...)`` / ``wire.frame_from_bin(...)``
    / ``InterDcTxn.from_bin(...)`` — any call whose terminal name is a
    wire-frame decoder."""
    f = node.func
    name = getattr(f, "attr", getattr(f, "id", None))
    return name in _DECODE_NAMES


def lint_decode_instants(root: str) -> List[str]:
    """ISSUE 7 rule: every function under the interdc/cluster packages
    that decodes a wire frame must record the arrival instant with a
    tracer span/instant — arrival-side time is half of every
    visibility-latency measurement.  Functions NAMED like a decoder
    (the wire.py definitions) are exempt; call sites are not."""
    problems: List[str] = []
    for rel_dir in _DECODE_DIRS:
        d = os.path.join(root, rel_dir)
        if not os.path.isdir(d):
            continue
        for fname in sorted(os.listdir(d)):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(d, fname)
            with open(path) as f:
                tree = ast.parse(f.read(), filename=path)
            for node in ast.walk(tree):
                if not isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                    continue
                if node.name in _DECODE_NAMES:
                    continue  # the decoder itself, not an arrival site
                decodes = any(
                    isinstance(c, ast.Call) and _is_decode_call(c)
                    for c in ast.walk(node))
                if decodes and not _is_instrumented(node):
                    problems.append(
                        f"{rel_dir}/{fname}::{node.name}: decodes a "
                        "wire frame without recording the arrival "
                        "instant — add tracer.instant/span (the "
                        "visibility SLOs need arrival-side time, "
                        "antidote_tpu/obs/spans.py)")
    return problems


def _is_fused_call(node: ast.Call) -> bool:
    """True for ``fused_read(...)`` / ``device_plane.fused_read(...)``
    — any call whose terminal name is a gathered-fold entry point."""
    f = node.func
    name = getattr(f, "attr", getattr(f, "id", None))
    return name in _FUSED_NAMES


def lint_fused_spans(root: str) -> List[str]:
    """ISSUE 8 rule: every function under antidote_tpu/mat/ that
    dispatches a gathered ``fused_read`` fold must carry a tracer
    span/instant — the serve plane's one-dispatch folds are the read
    path's hottest kernels and must stay on the serve-stage timeline.
    Functions NAMED like the fold (the device_plane definition) are
    exempt; call sites are not."""
    problems: List[str] = []
    for rel_dir in _FUSED_DIRS:
        d = os.path.join(root, rel_dir)
        if not os.path.isdir(d):
            continue
        for fname in sorted(os.listdir(d)):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(d, fname)
            with open(path) as f:
                tree = ast.parse(f.read(), filename=path)
            for node in ast.walk(tree):
                if not isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                    continue
                if node.name in _FUSED_NAMES:
                    continue  # the fold itself, not a dispatch site
                fuses = any(
                    isinstance(c, ast.Call) and _is_fused_call(c)
                    for c in ast.walk(node))
                if fuses and not _is_instrumented(node):
                    problems.append(
                        f"{rel_dir}/{fname}::{node.name}: dispatches "
                        "a gathered fused_read fold without a tracer "
                        "span/instant — the serve-stage latency "
                        "panels go dark (antidote_tpu/obs/spans.py)")
    return problems


def _is_sync_call(node: ast.Call) -> bool:
    """True for ``self.log.sync()`` / ``os.fsync(fd)`` /
    ``lib.oplog_sync(h)`` — any call whose terminal name is a
    durability barrier."""
    f = node.func
    name = getattr(f, "attr", getattr(f, "id", None))
    return name in _SYNC_NAMES


def lint_sync_spans(root: str) -> List[str]:
    """ISSUE 9 rule: every function under antidote_tpu/oplog/ with an
    fsync/sync call site must also carry a span/instant/annotation, so
    the durability barrier stays visible to the forensic plane as the
    group-commit plane moves it between threads.  Functions named
    ``sync`` (the DurableLog/_PyLog barrier definitions) are exempt;
    call sites are not."""
    problems: List[str] = []
    d = os.path.join(root, _SYNC_DIR)
    if not os.path.isdir(d):
        return problems
    for fname in sorted(os.listdir(d)):
        if not fname.endswith(".py"):
            continue
        path = os.path.join(d, fname)
        with open(path) as f:
            tree = ast.parse(f.read(), filename=path)
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            if node.name in _SYNC_NAMES:
                continue  # the barrier definition, not a call site
            syncs = any(
                isinstance(c, ast.Call) and _is_sync_call(c)
                for c in ast.walk(node))
            if syncs and not _is_instrumented(node):
                problems.append(
                    f"{_SYNC_DIR}/{fname}::{node.name}: calls the "
                    "durability barrier (sync/fsync) without a tracer "
                    "span/instant — commit-path disk stalls go dark "
                    "(antidote_tpu/obs/spans.py)")
    return problems


def _is_ckpt_io_call(node: ast.Call) -> bool:
    """True for ``self.ckpt.write_doc(...)`` / ``store.load_doc(...)``
    / ``self.log.truncate_below(...)`` — any call whose terminal name
    is a checkpoint-IO primitive."""
    f = node.func
    name = getattr(f, "attr", getattr(f, "id", None))
    return name in _CKPT_IO_NAMES


def lint_ckpt_spans(root: str) -> List[str]:
    """ISSUE 10 rule: every function under antidote_tpu/oplog/ with a
    checkpoint-IO call site (write_doc / load_doc / truncate_below)
    must also carry a span/instant/annotation — checkpoint writes,
    recovery loads, and log truncations are the cold-path disk moves
    the CKPT_* forensics attribute stalls to.  Functions named like
    the IO primitives are the definitions themselves and exempt."""
    problems: List[str] = []
    d = os.path.join(root, _CKPT_DIR)
    if not os.path.isdir(d):
        return problems
    for fname in sorted(os.listdir(d)):
        if not fname.endswith(".py"):
            continue
        path = os.path.join(d, fname)
        with open(path) as f:
            tree = ast.parse(f.read(), filename=path)
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            if node.name in _CKPT_IO_NAMES:
                continue  # the IO definition, not a call site
            does_io = any(
                isinstance(c, ast.Call) and _is_ckpt_io_call(c)
                for c in ast.walk(node))
            if does_io and not _is_instrumented(node):
                problems.append(
                    f"{_CKPT_DIR}/{fname}::{node.name}: performs "
                    "checkpoint IO (write_doc/load_doc/truncate_below) "
                    "without a tracer span/instant — checkpoint and "
                    "truncation stalls go dark "
                    "(antidote_tpu/obs/spans.py)")
    return problems


def _methods(tree: ast.Module, cls_name: str) -> Dict[str, ast.FunctionDef]:
    for node in tree.body:
        if isinstance(node, ast.ClassDef) and node.name == cls_name:
            return {n.name: n for n in node.body
                    if isinstance(n, (ast.FunctionDef,
                                      ast.AsyncFunctionDef))}
    return {}


def lint(root: str) -> List[str]:
    """All violations, as ``path::Class.method: <reason>`` strings."""
    problems: List[str] = []
    for rel, classes in sorted(ENTRY_POINTS.items()):
        path = os.path.join(root, rel)
        if not os.path.exists(path):
            problems.append(f"{rel}: file vanished (update ENTRY_POINTS "
                            "if the plane moved)")
            continue
        with open(path) as f:
            tree = ast.parse(f.read(), filename=path)
        for cls, methods in sorted(classes.items()):
            found = _methods(tree, cls)
            for m in methods:
                fn = found.get(m)
                if fn is None:
                    problems.append(
                        f"{rel}::{cls}.{m}: entry point missing "
                        "(renamed? update ENTRY_POINTS)")
                elif not _is_instrumented(fn):
                    problems.append(
                        f"{rel}::{cls}.{m}: no span/annotation — add "
                        "tracer.span/instant, prof.annotate, or "
                        "@traced")
    problems.extend(lint_kernel_spans(root))
    problems.extend(lint_publish_spans(root))
    problems.extend(lint_decode_instants(root))
    problems.extend(lint_fused_spans(root))
    problems.extend(lint_sync_spans(root))
    problems.extend(lint_ckpt_spans(root))
    return problems


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv: List[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    root = argv[0] if argv else repo_root()
    problems = lint(root)
    n_points = sum(len(ms) for classes in ENTRY_POINTS.values()
                   for ms in classes.values())
    if problems:
        print(f"trace_lint: {len(problems)} uninstrumented entry "
              f"point(s) of {n_points}:", file=sys.stderr)
        for p in problems:
            print(f"  {p}", file=sys.stderr)
        return 1
    print(f"trace_lint: OK — {n_points} entry points instrumented")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
