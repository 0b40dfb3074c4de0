#!/usr/bin/env python
"""concurrency_lint — the concurrency-discipline analyzer (ISSUE 11).

The bugs that cost review rounds in PRs 8-9 were not hygiene slips but
*concurrency-discipline* violations: an fsync or pickle under the
partition lock (found twice by human review), a plane constructed
outside its ``*_from_config`` factory (the gate_from_config lesson,
re-learned three times), and lock-order folklore distributed across
docstrings.  Cure's guarantees only hold if these invariants hold;
this lint encodes them as a static pass, the correctness-tooling
analogue of what trace_lint did for observability.  Three rule
families, all pure-ast (no imports of the package, runs in
milliseconds, needs no JAX):

**blocking-under-lock** [lock-blocking]: reconstruct lock-held regions
from ``with <lock>:`` blocks (a lock is any context expression whose
terminal name contains ``lock``, plus the per-module declaration table
``_DECLARED_LOCKS`` for condition variables named otherwise) and flag
calls that can block or burn unbounded time inside them: fsync/
fdatasync, the ``sync``/``oplog_sync`` durability barriers,
``pickle.dumps``/``loads``, ``os.replace``, ``time.sleep``,
``Condition.wait``/``Event.wait`` (waiting on the *held* condition is
the normal release-and-sleep idiom and exempt; waiting on any OTHER
object while holding a lock is the hazard), socket/transport sends,
device folds (``fused_read``, ``block_until_ready``,
``copy_to_host``), and this repo's own blocking primitives
(``wait_durable``, ``truncate_below``/``stage_truncate_below``,
``write_doc``/``load_doc``, ``checkpoint_now``).  The check
propagates through the intra-package call graph (a call under a lock
to a function that transitively blocks is the same bug with a stack
frame of indirection — exactly how the PR-8 fsync hid), resolving
``self.m()`` within the class and otherwise only names defined exactly
once in the package (ambiguity never invents a finding).  An inline
``# lock-ok: <reason>`` on the call line suppresses it, so every
surviving site is an *audited* decision; a ``# lock-ok`` without a
reason is itself a finding [lock-ok-reason] (the audit trail is the
point).

**lock-order** [lock-order]: extract nested acquisitions per function,
propagate acquisition sets through the same call graph, build the
global acquisition-order graph over lock identities
(``Class.attr`` / ``module:name``), and fail on cycles with the
witness edges.  Today the partition-lock -> log-handle-lock ->
``_pub_lock`` ordering is folklore; here it is a checked invariant.
Re-acquiring the SAME non-reentrant lock in one function (identical
``with`` expressions nested) is reported as a self-deadlock; self
edges that only arise through calls are ignored (two instances of the
same class are different locks).

**GIL policy** [gil-policy]: the native fabrics bind ONE shared
library twice, split by GIL policy (cluster/nativelink.py's ``_Lib``,
interdc/tcp.py's ``_FabLib``): blocking entry points — the condition
waits (``nl_wait``, ``nl_recv_batch``, ``nl_collect``), the
socket-binding/teardown class (``nl_create``, ``nl_shutdown``,
``fab_create``, ``fab_close``) and ``fab_publish`` (contends the hub
mutex against an event thread mid-send) — must bind via ``CDLL`` (GIL
released) and must never be CALLED inside a ``with <lock>:`` region
(a GIL-releasing call under a lock hands the lock's whole wait chain
to the scheduler); quick bookkeeping entry points must bind via
``PyDLL`` (a CDLL call re-acquires the GIL on return, costing up to a
scheduler timeslice against busy threads — measured at 4.4 ms per
start_request before the split).  The two tables below ARE the
policy: an entry point in neither is itself a finding, so a new
binding must be classified before it ships.  Keyed by the ASSIGNED
attribute name — ``self.nl_wait_probe = quick.nl_wait`` is the
deliberate zero-timeout GIL-held probe binding, a distinct entry
point with its own policy.

**collective launch discipline** [collective-lock]: runtime.py's
``COLLECTIVE_LOCK`` invariant — every multi-chip program launch must
hold the lock, or two threads' collectives interleave their ICI
programs and abort inside the XLA runtime — machine-enforced (ISSUE
20).  A name bound from a collective *builder* (``self._sm(...)``,
``shard_map_compat(...)``, possibly wrapped in ``jax.jit``/profiler
wrappers) is a launcher; calling it anywhere outside a ``with``
region whose items include ``COLLECTIVE_LOCK`` (either spelling),
``collective_guard(...)`` or ``_collective_cm()`` is a finding.  The
``lax.pmin/pmax/psum`` calls INSIDE a shard_map body are exempt by
construction — nested defs run at launch time, under the launcher's
lock, not at definition time.  ``# lock-ok: <reason>`` audits the
exceptions, same trail as [lock-blocking].

**knob routing + coverage** [knob-*]: direct construction of a
config-routed plane class (``_FACTORY_ROUTED``) anywhere in the
package outside its blessed factory module is an error — the
gate_from_config lesson, machine-enforced (benches and tests
deliberately construct baseline/variant assemblies and are not swept).
Additionally every ``config.<knob>`` / ``self.config.<knob>`` read in
the package must exist on :class:`antidote_tpu.config.Config`
[knob-unknown], and every declared knob must be read somewhere in
antidote_tpu/, benches/, tools/ or bench.py [knob-dead] — a knob
nothing reads is a promise the system does not keep.

Runs standalone (``python tools/concurrency_lint.py [root]``) and as
part of ``python -m tools.static_suite``; exit 0 = clean.  Fixture
tests: tests/unit/test_concurrency_lint.py.
"""

from __future__ import annotations

import ast
import os
import sys
from typing import Dict, List, Optional, Set, Tuple

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import astcommon  # noqa: E402 — shared call-graph + suppression infra

#: package swept for lock discipline and knob routing (tests and
#: benches intentionally build variant assemblies and hold the GIL in
#: single-threaded harnesses — they are exempt by design)
PACKAGE_DIR = "antidote_tpu"

#: extra dirs whose Config reads count for dead-knob coverage
KNOB_READ_DIRS = ("antidote_tpu", "benches", "tools")
KNOB_READ_FILES = ("bench.py",)

#: attribute/variable names that hold a lock although their name does
#: not contain "lock" — the per-module declaration table.  Grow this
#: when a module names a condition variable something new; the lint
#: cannot guess that ``_cv`` sleeps.
_DECLARED_LOCKS: Dict[str, Set[str]] = {
    "antidote_tpu/txn/node.py": {"_cond"},
    "antidote_tpu/mat/serve.py": {"_cond"},
    "antidote_tpu/interdc/sender.py": {"_cv"},
    "antidote_tpu/cluster/nativelink.py": {"_inflight_cv"},
    "antidote_tpu/interdc/tcp.py": {"_hub_cv"},
}

#: config-routed plane classes -> modules blessed to construct them
#: (the defining module is always blessed; listed here are the factory
#: homes).  Direct construction anywhere else in the package bypasses
#: the ``*_from_config`` routing and is an error.
_FACTORY_ROUTED: Dict[str, Tuple[str, ...]] = {
    # settings dataclasses: the *_from_config factories live in the
    # defining modules; nothing else may invent defaults
    "GroupSettings": ("antidote_tpu/oplog/log.py",),
    "CheckpointSettings": ("antidote_tpu/oplog/checkpoint.py",),
    "IngestSettings": ("antidote_tpu/mat/ingest.py",),
    "ServeSettings": ("antidote_tpu/mat/serve.py",),
    # plane classes: Node's partition factory is the one assembly path
    "DependencyGate": ("antidote_tpu/interdc/dep.py",),
    "CheckpointStore": ("antidote_tpu/oplog/checkpoint.py",
                        "antidote_tpu/txn/node.py"),
    "ReadServer": ("antidote_tpu/mat/serve.py",
                   "antidote_tpu/txn/node.py"),
    "DevicePlane": ("antidote_tpu/mat/device_plane.py",
                    "antidote_tpu/txn/node.py"),
    # fabric endpoints (ISSUE 12): Config.fabric_native routes them —
    # build_link and transport_from_config are the construction paths
    "NativeNodeLink": ("antidote_tpu/cluster/nativelink.py",
                       "antidote_tpu/cluster/node.py"),
    "TcpTransport": ("antidote_tpu/interdc/tcp.py",),
}

#: builtin-type method shadowing table — factored to astcommon (ISSUE
#: 15) so durability_lint's call resolution cannot drift from ours
_NO_RESOLVE = astcommon.NO_RESOLVE

#: owners whose ``publish`` is the inter-DC pub/sub wire send (the
#: trace_lint _PUBLISH_OWNERS contract); a meta entry's monotone
#: ``e.publish`` is host arithmetic, not a socket
_PUBLISH_OWNERS = ("transport", "bus")

#: terminal call names that ALWAYS block (or burn unbounded time)
_BLOCKING_ALWAYS = {
    "fsync": "fsync",
    "fdatasync": "fsync",
    "sync": "durability barrier",
    "oplog_sync": "durability barrier",
    "sendall": "socket send",
    "send_frame": "transport send",
    "fused_read": "device fold",
    "block_until_ready": "device fold",
    "copy_to_host": "device fold",
    # this repo's own blocking primitives: machine-enforces their
    # documented "must not hold the partition lock" contracts
    "wait_durable": "durability wait",
    "truncate_below": "log-suffix rewrite",
    "stage_truncate_below": "log-suffix rewrite",
    "stage_truncation": "log-suffix rewrite",
    "write_doc": "checkpoint write (pickle + fsync)",
    "load_doc": "checkpoint load",
    "checkpoint_now": "checkpoint cut+fold+persist",
    # streamed segment transfer (ISSUE 19): manifest/segment reads,
    # durable staging, and the staged-resize install are all file IO
    # (often fsync-bearing) and must never run under a partition lock
    "_load_segment": "segment read",
    "bundle_manifest": "manifest read",
    "read_segment_raw": "segment read",
    "ship_bundle": "bundle read",
    "install_bundle": "bundle install (write + fsync)",
    "stage_resize_checkpoint": "resize-checkpoint stage (fsync)",
    "commit_staged_resize_checkpoint": "resize-checkpoint install",
    "offer": "segment stage (write + fsync)",
    "commit": "bundle/txn commit",
}

#: terminal names that block only with a specific owner
_BLOCKING_OWNED = {
    ("pickle", "dumps"): "pickle under a lock",
    ("pickle", "loads"): "pickle under a lock",
    ("pickle", "dump"): "pickle under a lock",
    ("pickle", "load"): "pickle under a lock",
    ("os", "replace"): "atomic rename",
    ("time", "sleep"): "sleep",
    ("transport", "publish"): "transport publish",
    ("bus", "publish"): "transport publish",
}

#: Condition/Event wait verbs (exempt when waiting on the held lock)
_WAIT_NAMES = {"wait", "wait_for"}

#: context managers built AROUND an existing lock, which they acquire
#: and release and nothing else: ``self._locked = _TimedLock(
#: self._lock, ...)`` (txn/manager.py: the same lock, its contended
#: wait recorded as a ``pm_lock_wait`` span).  Classified like a
#: Condition around the lock, so ``with pm._locked:`` holds
#: ``pm._lock`` for every rule below.  ``_SiteCondition()`` (the same
#: file's ``pm._lock`` itself: a Condition that keeps its holds by
#: acquiring site) is classified as the Condition it is
_LOCK_WRAPPERS = {"_TimedLock", "_SiteCondition"}

#: collective-program builders: a name assigned from a call reaching
#: one of these is a multi-chip launcher and must only be CALLED under
#: a collective region ([collective-lock], runtime.py's invariant)
_COLLECTIVE_BUILDERS = {"_sm", "shard_map_compat"}

#: with-item terminal names that satisfy the collective-launch
#: discipline: the lock itself (either import spelling), the
#: device_plane guard helper, and the per-plane context manager
_COLLECTIVE_REGIONS = {"COLLECTIVE_LOCK", "_COLLECTIVE_LOCK",
                       "collective_guard", "_collective_cm"}

#: native fabric entry points that BLOCK (condition waits, socket
#: bind/teardown, mutex contention against event threads): must bind
#: via ctypes.CDLL — the GIL is released for the call — and must never
#: be called inside a lock region (module docstring, [gil-policy]).
#: Keyed by the ASSIGNED attribute name, so the deliberate GIL-held
#: probe rebindings (nl_wait_probe = quick.nl_wait) classify
#: separately.
_GIL_BLOCKING = {
    "nl_create": "socket bind",
    "nl_wait": "reply condition wait",
    "nl_recv_batch": "inbound-request condition wait",
    "nl_collect": "fan-out collect wait",
    "nl_shutdown": "event-thread join",
    "fab_create": "socket bind",
    "fab_publish": "hub-mutex send contention",
    "fab_sub_count": "hub-mutex contention against the event "
                     "thread's send sweep",
    "fab_queued_bytes": "hub-mutex contention against the event "
                        "thread's send sweep",
    "fab_close": "event-thread join",
    # telemetry drains (ISSUE 16): bulk memcpy of up to 128 KiB out of
    # the flight-recorder ring — long enough to CDLL, and never wanted
    # inside a lock region anyway (they ride gauge/gossip cadences)
    "nl_tel_drain": "telemetry ring bulk copy",
    "fab_tel_drain": "telemetry ring bulk copy",
}

#: native fabric entry points that only do bookkeeping under the
#: endpoint mutex (whose holders never block): must bind via
#: ctypes.PyDLL — a CDLL call would pay a GIL re-acquisition (up to a
#: scheduler timeslice against busy threads) for microseconds of C
_GIL_QUICK = {
    "nl_port", "nl_set_peer", "nl_send", "nl_cancel", "nl_drop_peer",
    "nl_reply", "nl_free", "nl_publish", "nl_publish_clear",
    "nl_counters", "nl_pub_gen", "nl_wait_probe", "nl_collect_probe",
    "fab_port",
    # telemetry cursor/enable (ISSUE 16): atomics-only — no mutex, no
    # syscall; the watchdog probes them from Python-held paths
    "nl_tel_cursor", "nl_tel_enable", "fab_tel_cursor",
    "fab_tel_enable",
}


#: call-name extraction — shared with durability_lint (astcommon)
_terminal = astcommon.terminal

#: one parsed module + its ``# lock-ok`` suppressions (tokenize-based
#: COMMENT scan, comment-only lines attach to the next code line —
#: see astcommon.FileInfo, factored out for durability_lint's dur-ok)
_FileInfo = astcommon.FileInfo


def _expr_key(node: ast.expr) -> str:
    """Stable identity of a lock expression (``self._lock`` ==
    ``self._lock``) — ast.dump is deterministic for our purposes."""
    return ast.dump(node)


class _Func:
    """One function's concurrency facts."""

    def __init__(self, rel: str, cls: Optional[str], node):
        self.rel = rel
        self.cls = cls
        self.node = node
        self.name = node.name
        #: lock ids acquired directly (with-statements)
        self.direct_locks: Set[str] = set()
        #: (held_tuple, lock_id, lineno) per acquisition, for nesting
        #: edges and self-deadlock detection
        self.acquisitions: List[Tuple[Tuple[str, ...], str, int,
                                      str]] = []
        #: direct blocking facts: (kind, what, lineno, wait_lock_id)
        #: wait_lock_id is the waited-on lock for wait verbs (None for
        #: unconditional blockers) — the caller-side exemption key
        self.blocking: List[Tuple[str, str, int, Optional[str]]] = []
        #: call sites: (callee_name, owner_name, lineno, held_tuple)
        self.calls: List[Tuple[str, Optional[str], int,
                               Tuple[str, ...]]] = []

    @property
    def qual(self) -> str:
        return f"{self.cls}.{self.name}" if self.cls else self.name


class _Analyzer:
    def __init__(self, root: str):
        self.root = root
        self.files: Dict[str, _FileInfo] = {}
        self.funcs: List[_Func] = []
        #: name/class call-resolution indices (astcommon.CallIndex)
        self.calls = astcommon.CallIndex()
        #: lock attr -> classes assigning it (owner-type heuristic)
        self.attr_owners: Dict[str, Set[str]] = {}
        #: (class, cv_attr) -> lock_attr for condition variables built
        #: AROUND an existing lock (``self._cv =
        #: threading.Condition(self._lock)`` shares the lock — waiting
        #: on the cv while holding the lock is the release-and-sleep
        #: idiom, not a second lock)
        self.cond_alias: Dict[Tuple[str, str], str] = {}
        #: (owning class or None, attr/name) -> "Lock"|"RLock"|
        #: "Condition"|"Event".  Keyed by CLASS, not bare attr:
        #: ``_lock`` is a Lock in one class and an RLock in another,
        #: and a first-hit attr lookup would misclassify every other
        #: class's lock.
        self.lock_kinds: Dict[Tuple[Optional[str], str], str] = {}

    # ------------------------------------------------------------ parse

    def load(self) -> List[str]:
        self.files, problems = astcommon.load_package(
            self.root, PACKAGE_DIR, marker="lock-ok")
        # pass 1: class metadata (lock attrs, Condition aliases) from
        # EVERY file — the function scan below resolves lock identity
        # across modules, so it must see the whole package's metadata
        for rel in sorted(self.files):
            self._collect_meta(self.files[rel])
        # pass 2: per-function concurrency facts
        for rel in sorted(self.files):
            self._collect_funcs(self.files[rel])
        for fn in self.funcs:
            self.calls.add(fn)
        return problems

    def _collect_funcs(self, info: _FileInfo) -> None:
        def walk(node, cls: Optional[str]):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.ClassDef):
                    walk(child, child.name)
                elif isinstance(child, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                    fn = _Func(info.rel, cls, child)
                    self.funcs.append(fn)
                    self._scan_func(info, fn)
                    walk(child, cls)  # nested defs: own lock scope
                else:
                    walk(child, cls)

        walk(info.tree, None)

    def _collect_meta(self, info: _FileInfo) -> None:
        """ONE scan per lock-object assignment records every fact the
        analyzer keeps about it: the owning class (obj.attr identity
        resolution), the kind (Lock/RLock/... — self-deadlock
        reporting skips reentrant locks), and Condition-around-a-lock
        aliases.  A single traversal on purpose: a new lock flavor
        added to one table but missed by another would make kind and
        owner resolution silently disagree."""

        def scan(body, cls):
            for node in body:
                if isinstance(node, ast.ClassDef):
                    scan(node.body, node.name)
                    continue
                for sub in ast.walk(node):
                    if not (isinstance(sub, ast.Assign)
                            and isinstance(sub.value, ast.Call)):
                        continue
                    kind = _terminal(sub.value.func)
                    if kind in _LOCK_WRAPPERS:
                        kind = "Condition"
                    if kind not in ("Lock", "RLock", "Condition",
                                    "Event"):
                        continue
                    inner = _terminal(sub.value.args[0]) \
                        if kind == "Condition" and sub.value.args \
                        else None
                    for t in sub.targets:
                        name = _terminal(t)
                        if not name:
                            continue
                        self.lock_kinds[(cls, name)] = kind
                        if cls:
                            self.attr_owners.setdefault(
                                name, set()).add(cls)
                            if inner:
                                self.cond_alias[(cls, name)] = inner

        scan(info.tree.body, None)

    # --------------------------------------------------- lock identity

    def _is_lock_expr(self, info: _FileInfo, node: ast.expr) -> bool:
        name = _terminal(node)
        if name is None:
            return False
        declared = _DECLARED_LOCKS.get(info.rel, set())
        return "lock" in name.lower() or name in declared

    def _lock_id(self, info: _FileInfo, fn: _Func,
                 node: ast.expr) -> str:
        name = _terminal(node)
        if isinstance(node, ast.Attribute):
            owner = node.value
            if isinstance(owner, ast.Name) and owner.id == "self":
                cls = fn.cls or info.rel
                name = self.cond_alias.get((fn.cls, name), name) \
                    if fn.cls else name
                return f"{cls}.{name}"
            owners = self.attr_owners.get(name, set())
            if len(owners) == 1:
                cls = next(iter(owners))
                name = self.cond_alias.get((cls, name), name)
                return f"{cls}.{name}"
            return f"{_terminal(owner)}.{name}"
        return f"{info.rel}:{name}"

    def _lock_kind(self, lock_id: str) -> str:
        """Kind for a lock identity: exact (class, attr) declaration
        first; else the attr-wide consensus across the package; on a
        CONFLICT (same attr is Lock here, RLock there) answer RLock —
        ambiguity must never invent a self-deadlock finding."""
        if ":" in lock_id:
            cls, attr = None, lock_id.rsplit(":", 1)[-1]
        else:
            cls, attr = lock_id.rsplit(".", 1)
        if cls is not None and (cls, attr) in self.lock_kinds:
            return self.lock_kinds[(cls, attr)]
        kinds = {k for (c, a), k in self.lock_kinds.items()
                 if a == attr}
        if len(kinds) == 1:
            return kinds.pop()
        if kinds:
            return "RLock"
        return "Lock"

    # ----------------------------------------------------- per-function

    def _scan_func(self, info: _FileInfo, fn: _Func) -> None:
        """Walk one function body tracking the with-lock stack; nested
        defs are skipped (their body runs at call time, not under this
        region — they are scanned as their own functions)."""

        def classify(call: ast.Call
                     ) -> Optional[Tuple[str, str, Optional[str]]]:
            f = call.func
            name = _terminal(f)
            if name is None:
                return None
            owner = _terminal(f.value) if isinstance(
                f, ast.Attribute) else None
            if name in _WAIT_NAMES and isinstance(f, ast.Attribute):
                wl = self._lock_id(info, fn, f.value) \
                    if self._is_lock_expr(info, f.value) else \
                    f"{owner}.{name}"
                return ("wait", f"{owner}.{name}", wl)
            if name in _GIL_BLOCKING and fn.name != name:
                # [gil-policy]: a GIL-releasing native call under a
                # lock hands the lock's whole wait chain to the
                # scheduler (and fab_publish can contend an event
                # thread mid-send for the send's duration)
                return ("gil", "GIL-releasing native call "
                               f"{name} ({_GIL_BLOCKING[name]})", None)
            if name in _BLOCKING_ALWAYS and fn.name != name:
                # a function NAMED like the primitive is its
                # definition/wrapper, not a call-under-lock site
                return ("blocking", _BLOCKING_ALWAYS[name], None)
            if owner is not None and (owner, name) in _BLOCKING_OWNED:
                return ("blocking", _BLOCKING_OWNED[(owner, name)],
                        None)
            return None

        def visit(node, held: Tuple[Tuple[str, str], ...]):
            # held: ((lock_id, expr_key), ...) outermost first
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef,
                                      ast.Lambda)):
                    continue
                if isinstance(child, (ast.With, ast.AsyncWith)):
                    new_held = held
                    for item in child.items:
                        ctx = item.context_expr
                        if self._is_lock_expr(info, ctx):
                            lid = self._lock_id(info, fn, ctx)
                            fn.direct_locks.add(lid)
                            fn.acquisitions.append(
                                (tuple(h[0] for h in new_held), lid,
                                 child.lineno, _expr_key(ctx)))
                            new_held = new_held + (
                                (lid, _expr_key(ctx)),)
                    visit(child, new_held)
                    continue
                if isinstance(child, ast.Call):
                    cls = classify(child)
                    # a reasoned `# lock-ok` ON the blocking line
                    # audits it for every lock context — callers'
                    # propagated findings are covered by the one
                    # source-site audit (the legacy inline-fsync
                    # pattern: one audited line, five call sites)
                    if cls is not None and not info.suppress.get(
                            child.lineno):
                        kind, what, wl = cls
                        fn.blocking.append(
                            (kind, what, child.lineno, wl))
                    name = _terminal(child.func)
                    owner = _terminal(child.func.value) if isinstance(
                        child.func, ast.Attribute) else None
                    if name:
                        fn.calls.append(
                            (name, owner, child.lineno,
                             tuple(h[0] for h in held)))
                visit(child, held)

        visit(fn.node, ())
        # waits on a lock the region holds are the release-and-sleep
        # idiom: drop them from the blocking set entirely when the
        # waited lock is held at the site (re-derived here with the
        # held stack per line)
        held_at: Dict[int, Set[str]] = {}
        self._held_lines(fn.node, info, fn, (), held_at)
        fn.blocking = [
            (k, w, ln, wl) for (k, w, ln, wl) in fn.blocking
            if not (k == "wait" and wl in held_at.get(ln, set()))]

    def _held_lines(self, node, info, fn, held, out) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef,
                                  ast.AsyncFunctionDef, ast.Lambda)):
                continue
            new_held = held
            if isinstance(child, (ast.With, ast.AsyncWith)):
                for item in child.items:
                    ctx = item.context_expr
                    if self._is_lock_expr(info, ctx):
                        new_held = new_held + (
                            self._lock_id(info, fn, ctx),)
            for n in ast.walk(child):
                ln = getattr(n, "lineno", None)
                if ln is not None:
                    out.setdefault(ln, set()).update(new_held)
            if isinstance(child, (ast.With, ast.AsyncWith)):
                self._held_lines(child, info, fn, new_held, out)
            else:
                self._held_lines(child, info, fn, held, out)

    # ------------------------------------------------- call resolution

    def resolve(self, caller: _Func, name: str,
                owner: Optional[str]) -> Optional[_Func]:
        return self.calls.resolve(caller.cls, name, owner)

    # ------------------------------------------ transitive blocking set

    def _transitive_blocking(self) -> Dict[
            _Func, List[Tuple[str, str, Optional[str], str]]]:
        """func -> [(kind, what, wait_lock, via)]: every blocking fact
        reachable from it through resolvable calls, with the access
        path ("a -> b -> fsync") for the finding message."""
        memo: Dict[_Func, List] = {}

        def go(fn: _Func, stack: Set[_Func]):
            if fn in memo:
                return memo[fn]
            if fn in stack:
                return []
            memo[fn] = out = [
                (k, w, wl, f"{fn.qual}:{ln}")
                for (k, w, ln, wl) in fn.blocking]
            stack.add(fn)
            for (name, owner, _ln, _held) in fn.calls:
                callee = self.resolve(fn, name, owner)
                if callee is None or callee is fn:
                    continue
                for (k, w, wl, via) in go(callee, stack):
                    out.append((k, w, wl, f"{fn.qual} -> {via}"))
            stack.discard(fn)
            # dedupe by (kind, what, wait lock): one witness is enough
            seen: Set[Tuple] = set()
            uniq = []
            for item in out:
                key = item[:3]
                if key not in seen:
                    seen.add(key)
                    uniq.append(item)
            memo[fn] = uniq
            return uniq

        for fn in self.funcs:
            go(fn, set())
        return memo

    # ------------------------------------------------ rule 1: blocking

    def lint_blocking(self) -> List[str]:
        problems: List[str] = []
        trans = self._transitive_blocking()
        for fn in self.funcs:
            info = self.files[fn.rel]
            # direct blocking calls inside a lock region
            held_at: Dict[int, Set[str]] = {}
            self._held_lines(fn.node, info, fn, (), held_at)
            for (kind, what, ln, wl) in fn.blocking:
                held = held_at.get(ln, set())
                if not held:
                    continue
                if kind == "wait" and wl in held:
                    continue
                if self._suppressed(info, ln):
                    continue
                tag = "gil-policy" if kind == "gil" else "lock-blocking"
                problems.append(
                    f"{fn.rel}:{ln}: [{tag}] {what} "
                    f"({fn.qual}) inside lock region "
                    f"{{{', '.join(sorted(held))}}} — move it out or "
                    "audit with `# lock-ok: <reason>`")
            # calls under a lock to transitively-blocking functions
            for (name, owner, ln, held) in fn.calls:
                if not held:
                    continue
                callee = self.resolve(fn, name, owner)
                if callee is None or callee is fn:
                    continue
                facts = trans.get(callee, [])
                hit = next(
                    (f for f in facts
                     if not (f[0] == "wait" and self._wait_covered(
                         f[2], held, owner))), None)
                if hit is None:
                    continue
                if self._suppressed(info, ln):
                    continue
                kind, what, _wl, via = hit
                tag = "gil-policy" if kind == "gil" else "lock-blocking"
                problems.append(
                    f"{fn.rel}:{ln}: [{tag}] call to "
                    f"{name}() under {{{', '.join(sorted(held))}}} "
                    f"reaches a {what} ({via}) — move it out or "
                    "audit with `# lock-ok: <reason>`")
        return problems

    @staticmethod
    def _wait_covered(wl: Optional[str], held,
                      call_owner: Optional[str]) -> bool:
        """True when a propagated wait fact sleeps on a lock the call
        site already holds.  Exact id match first; otherwise the
        untyped-owner form: holding ``pm._lock`` while calling
        ``pm._wait_x()`` whose wait is ``PartitionManager._lock`` is
        the same object — the callee's ``self`` IS the call owner, so
        matching attribute + matching owner name covers it."""
        if wl is None:
            return False
        if wl in held:
            return True
        attr = wl.rsplit(".", 1)[-1]
        for h in held:
            if "." in h and h.rsplit(".", 1)[-1] == attr \
                    and h.rsplit(".", 1)[0] == call_owner:
                return True
        return False

    def _suppressed(self, info: _FileInfo, lineno: int) -> bool:
        return info.suppressed(lineno)

    def lint_lock_ok_reasons(self) -> List[str]:
        """A ``# lock-ok`` with no reason defeats the audit trail the
        suppression exists to create — itself a finding."""
        problems = []
        for rel in sorted(self.files):
            for ln, reason in self.files[rel].suppress_sites:
                if not reason:
                    problems.append(
                        f"{rel}:{ln}: [lock-ok-reason] `# lock-ok` "
                        "without a reason — write `# lock-ok: <why "
                        "this blocking call must stay under the "
                        "lock>`")
        return problems

    # ---------------------------------------------- rule 2: lock order

    def _transitive_locks(self) -> Dict[_Func, Set[str]]:
        memo: Dict[_Func, Set[str]] = {}

        def go(fn: _Func, stack: Set[_Func]) -> Set[str]:
            if fn in memo:
                return memo[fn]
            if fn in stack:
                return set()
            stack.add(fn)
            out = set(fn.direct_locks)
            for (name, owner, _ln, _held) in fn.calls:
                callee = self.resolve(fn, name, owner)
                if callee is not None and callee is not fn:
                    out |= go(callee, stack)
            stack.discard(fn)
            memo[fn] = out
            return out

        for fn in self.funcs:
            go(fn, set())
        return memo

    def lint_lock_order(self) -> List[str]:
        problems: List[str] = []
        edges: Dict[Tuple[str, str], str] = {}
        # direct nesting (and same-expression re-acquire)
        for fn in self.funcs:
            info = self.files[fn.rel]
            seen_exprs: List[Tuple[Tuple[str, ...], str, int, str]] \
                = fn.acquisitions
            for (held, lid, ln, ekey) in seen_exprs:
                if self._suppressed(info, ln):
                    continue
                for h in held:
                    if h == lid:
                        continue  # self edge via re-entry: see below
                    edges.setdefault(
                        (h, lid),
                        f"{fn.rel}:{ln} ({fn.qual}: {h} -> {lid})")
            # identical-expression nested re-acquire of a
            # non-reentrant lock: a guaranteed self-deadlock
            for (held, lid, ln, ekey) in seen_exprs:
                if self._suppressed(info, ln):
                    continue
                # find an enclosing acquisition with the same expr
                for (held2, lid2, ln2, ekey2) in seen_exprs:
                    if (ln2 < ln and ekey2 == ekey and lid2 == lid
                            and lid in held
                            and self._lock_kind(lid) != "RLock"):
                        problems.append(
                            f"{fn.rel}:{ln}: [lock-order] {fn.qual} "
                            f"re-acquires non-reentrant {lid} it "
                            f"already holds (first taken at line "
                            f"{ln2}) — self-deadlock")
                        break
        # held-across-call edges
        trans = self._transitive_locks()
        for fn in self.funcs:
            info = self.files[fn.rel]
            for (name, owner, ln, held) in fn.calls:
                if not held:
                    continue
                callee = self.resolve(fn, name, owner)
                if callee is None or callee is fn:
                    continue
                if self._suppressed(info, ln):
                    continue
                for lid in trans.get(callee, ()):
                    for h in held:
                        if h != lid:
                            edges.setdefault(
                                (h, lid),
                                f"{fn.rel}:{ln} ({fn.qual} holds {h},"
                                f" {name}() acquires {lid})")
        problems.extend(self._find_cycles(edges))
        return problems

    @staticmethod
    def _find_cycles(edges: Dict[Tuple[str, str], str]) -> List[str]:
        graph: Dict[str, Set[str]] = {}
        for (a, b) in edges:
            graph.setdefault(a, set()).add(b)
            graph.setdefault(b, set())
        problems = []
        color: Dict[str, int] = {}
        stack: List[str] = []

        def dfs(u: str) -> Optional[List[str]]:
            color[u] = 1
            stack.append(u)
            for v in sorted(graph[u]):
                if color.get(v, 0) == 1:
                    return stack[stack.index(v):] + [v]
                if color.get(v, 0) == 0:
                    cyc = dfs(v)
                    if cyc:
                        return cyc
            stack.pop()
            color[u] = 2
            return None

        for u in sorted(graph):
            if color.get(u, 0) == 0:
                cyc = dfs(u)
                if cyc:
                    witness = []
                    for a, b in zip(cyc, cyc[1:]):
                        witness.append(f"  {a} -> {b}: "
                                       f"{edges[(a, b)]}")
                    problems.append(
                        "[lock-order] acquisition-order cycle "
                        + " -> ".join(cyc) + "\n"
                        + "\n".join(witness))
                    break  # one witness cycle is actionable enough
        return problems

    # ------------------------------------ rule: collective launch lock

    def lint_collective_lock(self) -> List[str]:
        """Calls of names bound from a collective builder
        (``self._sm(...)`` / ``shard_map_compat(...)``, possibly
        wrapped in ``jax.jit``/profiler wrap calls) must sit inside a
        ``with`` region whose items include COLLECTIVE_LOCK,
        ``collective_guard(...)`` or ``_collective_cm()`` — two
        threads' interleaved multi-chip programs abort inside the XLA
        runtime, so runtime.py makes the lock the law and this rule
        makes the law checkable.  Nested defs and lambdas (the
        shard_map BODIES, where ``lax.pmin/pmax/psum`` live) are
        skipped: they execute at launch time under the launcher's
        region, not at definition time."""
        problems: List[str] = []

        def region_item(ctx: ast.expr) -> bool:
            f = ctx.func if isinstance(ctx, ast.Call) else ctx
            return _terminal(f) in _COLLECTIVE_REGIONS

        for fn in self.funcs:
            info = self.files[fn.rel]
            launchers: Set[str] = set()

            def scan(node, covered: bool):
                for child in ast.iter_child_nodes(node):
                    if isinstance(child, (ast.FunctionDef,
                                          ast.AsyncFunctionDef,
                                          ast.Lambda)):
                        continue
                    if isinstance(child, (ast.With, ast.AsyncWith)):
                        scan(child, covered or any(
                            region_item(i.context_expr)
                            for i in child.items))
                        continue
                    if isinstance(child, ast.Assign) and any(
                            isinstance(n, ast.Call)
                            and _terminal(n.func)
                            in _COLLECTIVE_BUILDERS
                            for n in ast.walk(child.value)):
                        for t in child.targets:
                            name = _terminal(t)
                            if name:
                                launchers.add(name)
                    if isinstance(child, ast.Call):
                        name = _terminal(child.func)
                        if name in launchers and not covered \
                                and not self._suppressed(
                                    info, child.lineno):
                            problems.append(
                                f"{fn.rel}:{child.lineno}: "
                                f"[collective-lock] multi-chip "
                                f"program {name}() launched outside "
                                f"a COLLECTIVE_LOCK region "
                                f"({fn.qual}) — wrap the launch in "
                                "`with COLLECTIVE_LOCK:` / "
                                "`collective_guard(dev)` / "
                                "`self._collective_cm()` or audit "
                                "with `# lock-ok: <reason>`")
                    scan(child, covered)

            scan(fn.node, False)
        return problems

    # ----------------------------------------- rule: GIL binding policy

    def lint_gil_bindings(self) -> List[str]:
        """Every ``x.attr = <dll_var>.<sym>`` binding where <dll_var>
        was assigned from ``ctypes.CDLL(...)`` / ``ctypes.PyDLL(...)``
        must agree with the policy tables, keyed by the ASSIGNED
        attribute name (``nl_wait_probe = quick.nl_wait`` is the
        deliberate GIL-held probe, its own entry point).  A bound name
        in neither table is itself a finding — the tables ARE the
        policy, and an unclassified binding means nobody decided."""
        problems: List[str] = []
        for rel in sorted(self.files):
            info = self.files[rel]
            # dll handle vars per file: name -> "CDLL" | "PyDLL"
            dll_vars: Dict[str, str] = {}
            for node in ast.walk(info.tree):
                if not (isinstance(node, ast.Assign)
                        and isinstance(node.value, ast.Call)):
                    continue
                kind = _terminal(node.value.func)
                if kind not in ("CDLL", "PyDLL"):
                    continue
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        dll_vars[t.id] = kind
            if not dll_vars:
                continue
            for node in ast.walk(info.tree):
                if not isinstance(node, ast.Assign):
                    continue
                v = node.value
                if not (isinstance(v, ast.Attribute)
                        and isinstance(v.value, ast.Name)
                        and v.value.id in dll_vars):
                    continue
                policy = dll_vars[v.value.id]
                for t in node.targets:
                    bound = _terminal(t)
                    if bound is None:
                        continue
                    if self._suppressed(info, node.lineno):
                        continue
                    if bound in _GIL_BLOCKING:
                        if policy != "CDLL":
                            problems.append(
                                f"{rel}:{node.lineno}: [gil-policy] "
                                f"blocking native entry point {bound} "
                                f"({_GIL_BLOCKING[bound]}) bound via "
                                "PyDLL — it holds the GIL across a "
                                "blocking call; bind via CDLL")
                    elif bound in _GIL_QUICK:
                        if policy != "PyDLL":
                            problems.append(
                                f"{rel}:{node.lineno}: [gil-policy] "
                                f"quick native entry point {bound} "
                                "bound via CDLL — the GIL "
                                "re-acquisition on return costs up to "
                                "a scheduler timeslice per call; bind "
                                "via PyDLL")
                    else:
                        problems.append(
                            f"{rel}:{node.lineno}: [gil-policy] "
                            f"unclassified native entry point {bound} "
                            "bound from a ctypes library — add it to "
                            "_GIL_BLOCKING or _GIL_QUICK (the tables "
                            "are the policy)")
        return problems

    # -------------------------------------- rule 3: knob routing + cov

    def lint_knobs(self) -> List[str]:
        problems: List[str] = []
        # construction routing
        for fn_rel in sorted(self.files):
            info = self.files[fn_rel]
            for node in ast.walk(info.tree):
                if not isinstance(node, ast.Call):
                    continue
                name = _terminal(node.func)
                blessed = _FACTORY_ROUTED.get(name or "")
                if blessed is None:
                    continue
                if fn_rel.replace(os.sep, "/") in blessed:
                    continue
                if self._suppressed(info, node.lineno):
                    continue
                problems.append(
                    f"{fn_rel}:{node.lineno}: [knob-routing] direct "
                    f"{name}(...) construction outside its factory "
                    f"({', '.join(blessed)}) — route through the "
                    "*_from_config path (the gate_from_config "
                    "lesson)")
        # knob existence + dead knobs
        knobs = self._config_knobs()
        if knobs is None:
            problems.append(
                f"{PACKAGE_DIR}/config.py: [knob-unknown] Config "
                "class not found — knob coverage cannot run")
            return problems
        reads: Set[str] = set()
        for rel, tree in self._knob_read_trees():
            in_pkg = rel.startswith(PACKAGE_DIR)
            for node in ast.walk(tree):
                # version-tolerant reads spell the knob as a string:
                # getattr(config, "knob", default) — count them too,
                # and hold their names to the same existence bar (a
                # typo here is WORSE: the default hides it forever)
                if isinstance(node, ast.Call) \
                        and isinstance(node.func, ast.Name) \
                        and node.func.id == "getattr" \
                        and len(node.args) >= 2 \
                        and self._is_config_owner(node.args[0]) \
                        and isinstance(node.args[1], ast.Constant) \
                        and isinstance(node.args[1].value, str):
                    attr = node.args[1].value
                elif isinstance(node, ast.Attribute) \
                        and self._is_config_owner(node.value):
                    attr = node.attr
                else:
                    continue
                reads.add(attr)
                if in_pkg and attr not in knobs \
                        and rel != f"{PACKAGE_DIR}/config.py":
                    problems.append(
                        f"{rel}:{node.lineno}: [knob-unknown] "
                        f"Config.{attr} is read but not declared "
                        "on Config — a typo here silently falls "
                        "through to defaults")
        for knob in sorted(knobs - reads):
            problems.append(
                f"{PACKAGE_DIR}/config.py: [knob-dead] Config."
                f"{knob} is declared but never read anywhere in "
                f"{', '.join(KNOB_READ_DIRS + KNOB_READ_FILES)} — "
                "route it or delete it")
        return problems

    def _config_knobs(self) -> Optional[Set[str]]:
        rel = f"{PACKAGE_DIR}/config.py"
        info = self.files.get(rel)
        if info is None:
            return None
        for node in ast.walk(info.tree):
            if isinstance(node, ast.ClassDef) and node.name == "Config":
                out = set()
                for st in node.body:
                    if isinstance(st, ast.AnnAssign) and isinstance(
                            st.target, ast.Name):
                        out.add(st.target.id)
                return out
        return None

    def _knob_read_trees(self):
        for d in KNOB_READ_DIRS:
            base = os.path.join(self.root, d)
            if not os.path.isdir(base):
                continue
            for dirpath, dirnames, filenames in os.walk(base):
                dirnames[:] = [x for x in dirnames
                               if x not in ("__pycache__", "_build")]
                for fname in sorted(filenames):
                    if not fname.endswith(".py"):
                        continue
                    path = os.path.join(dirpath, fname)
                    rel = os.path.relpath(path, self.root)
                    rel = rel.replace(os.sep, "/")
                    if rel in self.files:
                        yield rel, self.files[rel].tree
                        continue
                    try:
                        with open(path) as f:
                            yield rel, ast.parse(f.read())
                    except SyntaxError:
                        continue  # analysis_gate owns syntax findings
        for fname in KNOB_READ_FILES:
            path = os.path.join(self.root, fname)
            if os.path.exists(path):
                try:
                    with open(path) as f:
                        yield fname, ast.parse(f.read())
                except SyntaxError:
                    continue

    @staticmethod
    def _is_config_owner(owner: ast.expr) -> bool:
        """True when ``owner`` is a Config-holding expression:
        bare ``config``/``cfg`` or ``<obj>.config`` / ``<obj>.cfg`` /
        ``<obj>._config`` where <obj> is a plain name that is not a
        known foreign module (``jax.config.update`` is jax's)."""
        if isinstance(owner, ast.Name):
            return owner.id in ("config", "cfg")
        if isinstance(owner, ast.Attribute):
            if owner.attr not in ("config", "cfg", "_config"):
                return False
            root = owner.value
            while isinstance(root, ast.Attribute):
                root = root.value
            return isinstance(root, ast.Name) \
                and root.id not in ("jax", "_jax")
        return False


def lint(root: str) -> List[str]:
    an = _Analyzer(root)
    problems = an.load()
    problems.extend(an.lint_blocking())
    problems.extend(an.lint_lock_ok_reasons())
    problems.extend(an.lint_lock_order())
    problems.extend(an.lint_collective_lock())
    problems.extend(an.lint_gil_bindings())
    problems.extend(an.lint_knobs())
    return problems


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    root = argv[0] if argv else repo_root()
    problems = lint(root)
    if problems:
        print(f"concurrency_lint: {len(problems)} finding(s):",
              file=sys.stderr)
        for p in problems:
            print(f"  {p}", file=sys.stderr)
        return 1
    print("concurrency_lint: OK — lock regions, acquisition order, "
          "and knob routing are clean")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
