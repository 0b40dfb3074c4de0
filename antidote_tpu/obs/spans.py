"""Txid-correlated spans — the Dapper-style trace tree, in-process.

One :class:`Tracer` per process holds finished spans in a bounded ring.
A span is opened with :meth:`Tracer.span` (a context manager) or
recorded point-in-time with :meth:`Tracer.instant`; nesting is tracked
per thread, and the cross-thread / cross-plane correlator is the
transaction id carried in ``txid`` — the coordinator, log, device
plane, inter-DC sender/deliverer, and dependency gate all stamp the
same txid, so one committed transaction's spans assemble into a tree
spanning every plane it touched (ISSUE 1 tentpole).

Sampling is DETERMINISTIC per txid (crc32, not ``hash()`` — the latter
is salted per process, and a federation's DCs must agree on which
transactions are traced so a sampled txn's tree is complete across
processes).  Untagged spans (batched device flushes, GC, heartbeats)
are thinned to ~rate by a hashed call counter at partial rates —
enough background context around the per-txn trees without letting a
hot untagged path flood the ring — and recorded on every call only
when the rate is 1.0.

A span is **work** (its thread runs), **wait** (its thread sleeps on a
lock, a condition or the device) or a request's **root**
(:meth:`Tracer.root`: one wire message, frame read to answer sent).
Every span under a root carries the root's request id in ``req``, and
a span opened without a txid inherits its parent's, so one served
request is one tree under one identifier.  Only the outermost site
decides: a call chain that is being traced records all of its spans,
and under a root that declined no untagged site records (a
transaction's own spans still follow its txid, which every DC
agrees on).

While a profiler capture is open (``obs.prof.start`` calls
:meth:`Tracer.capture_begin`) every span records, and the innermost
open *work* span of each thread holds a ``jax.profiler.
TraceAnnotation`` of its name: a child's entry closes its parent's
annotation and its exit opens it again, so the ``.xplane.pb`` holds
one flat run of self-time segments per thread, beside the device
plane and on its clock.  Wait spans and roots are never annotated: a
thread asleep under a device gap did not cause it.  The one nested
annotation is a span a callback times (:meth:`Tracer.open_timed`: the
collector's pause, obs/host.py), which opens inside whatever its
thread was running.
:func:`summarize` reduces a capture's spans to per-name totals, self
times and ``host_busy_s``.

Export is Chrome ``trace_event`` JSON ("X" complete events), loadable
in Perfetto / chrome://tracing next to the JAX profiler captures
(antidote_tpu/obs/prof.py); ``ts`` is epoch microseconds so captures
from several processes align on one timeline.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
import zlib
from collections import deque
from typing import Any, Dict, List, Optional

from antidote_tpu.config import Config as _Config
from antidote_tpu.obs.events import _jsonable

#: single source for the tracer knob defaults — Config declares them,
#: the process-global tracer below starts from them, and Node pushes
#: only non-default Config values (obs.configure)
_CFG_DEFAULTS = _Config()


class Span:
    """One finished span (immutable once in the ring)."""

    __slots__ = ("span_id", "parent_id", "name", "cat", "txid",
                 "start_us", "dur_us", "tid", "args", "kind", "req")

    def __init__(self, span_id: int, parent_id: Optional[int], name: str,
                 cat: str, txid, start_us: int, dur_us: int, tid: int,
                 args: Dict[str, Any], kind: str = "work", req=None):
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.cat = cat
        self.txid = txid
        self.start_us = start_us
        self.dur_us = dur_us
        self.tid = tid
        self.args = args
        #: "work", "wait" or "root" (module docstring)
        self.kind = kind
        #: the request id of the root this span was recorded under
        self.req = req

    def __repr__(self) -> str:  # test/debug ergonomics
        return (f"Span({self.name!r}, cat={self.cat!r}, "
                f"txid={self.txid!r}, dur_us={self.dur_us})")

    def to_trace_event(self) -> Dict[str, Any]:
        args = {k: _jsonable(v) for k, v in self.args.items()}
        if self.txid is not None:
            args["txid"] = _jsonable(self.txid)
        if self.req is not None:
            args["req"] = _jsonable(self.req)
        if self.kind != "work":
            args["kind"] = self.kind
        return {"name": self.name, "cat": self.cat, "ph": "X",
                "ts": self.start_us, "dur": self.dur_us,
                "pid": os.getpid(), "tid": self.tid, "args": args}


_SPAN_IDS = itertools.count(1)
_tls = threading.local()


def txid_decision(txid, rate: float) -> bool:
    """The deterministic per-txid sampling decision at ``rate`` —
    crc32 of the txid repr, stable across processes.  Exposed as a
    module function because the wire's trace header (ISSUE 7) carries
    the ORIGIN's sample rate: a receiver replays the origin's decision
    through this same function so a sampled txn's remote-side spans
    record even when the local rate differs."""
    if rate <= 0.0:
        return False
    if rate >= 1.0:
        return True
    return (zlib.crc32(repr(txid).encode()) % 10_000) < rate * 10_000


class _NullSpan:
    """Shared no-op context for unsampled call sites (zero alloc)."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


class _Declined:
    """What :meth:`Tracer.root` hands a request that does not record:
    until it exits, the thread's untagged sites are null contexts
    without a sampling decision each, so a request records whole or
    not at all and an unrecorded one pays two attribute reads a site.
    Shared, like ``_NULL``; the count lives on the thread."""

    __slots__ = ()

    def __enter__(self):
        _tls.declined = getattr(_tls, "declined", 0) + 1
        return None

    def __exit__(self, *exc):
        _tls.declined -= 1
        return False


_DECLINED = _Declined()


class _LiveSpan:
    """Open span: context manager pushing itself on the thread's stack."""

    __slots__ = ("_tracer", "name", "cat", "txid", "args", "kind", "req",
                 "_start_ns", "_parent", "_ann", "span_id")

    def __init__(self, tracer: "Tracer", name: str, cat: str, txid,
                 args: Dict[str, Any], kind: str = "work"):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.txid = txid
        self.args = args
        self.kind = kind
        self.req = txid if kind == "root" else None
        self._ann = None
        self.span_id = next(_SPAN_IDS)

    def _annotate(self, on: bool) -> None:
        """Open or close this span's profiler annotation (work spans
        inside a capture only; module docstring)."""
        if not on:
            if self._ann is not None:
                self._ann.__exit__(None, None, None)
                self._ann = None
            return
        factory = self._tracer._annotation
        if factory is not None and self.kind == "work":
            self._ann = factory(self.name)
            self._ann.__enter__()

    def __enter__(self):
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        # the stack holds the LIVE span objects (not bare ids): the
        # kernel-span layer (obs/prof.py) reads the innermost open
        # span's txid/span_id via Tracer.current to attach device
        # kernels to the active txn's tree
        parent = stack[-1] if stack else None
        self._parent = parent
        if parent is not None:
            if self.req is None:
                self.req = parent.req
            if self.txid is None:
                self.txid = parent.txid
            parent._annotate(False)
        stack.append(self)
        self._annotate(True)
        self._start_ns = time.time_ns()
        return self

    def __exit__(self, *exc):
        end_ns = time.time_ns()
        self._annotate(False)
        stack = getattr(_tls, "stack", None)
        if stack and stack[-1] is self:
            stack.pop()
        parent = self._parent
        if parent is not None:
            parent._annotate(True)
        # both ends floored to whole microseconds of the same clock, so
        # spans nested or in sequence stay so in what is recorded
        start_us = self._start_ns // 1000
        self._tracer._add(Span(
            self.span_id, parent.span_id if parent is not None else None,
            self.name, self.cat, self.txid, start_us,
            end_ns // 1000 - start_us, threading.get_ident(),
            self.args, self.kind, self.req))
        return False


class Tracer:
    """Bounded ring of finished spans + the sampling decision."""

    def __init__(self,
                 capacity: int = _CFG_DEFAULTS.trace_capacity,
                 sample_rate: float = _CFG_DEFAULTS.trace_sample_rate):
        #: memoized per-txid decisions — a txn's id is checked at every
        #: plane it crosses (~8 call sites), and the crc32-of-repr is
        #: the dominant cost of an UNsampled txn's whole trace overhead
        self._decision_cache: Dict[Any, bool] = {}
        #: thins untagged (txid-less) spans at partial sample rates
        self._untagged_seq = itertools.count()
        self.sample_rate = sample_rate
        self._capacity = capacity
        self._spans: deque = deque(maxlen=capacity)
        self._lock = threading.Lock()
        #: spans a callback recorded (close_timed), queued without the
        #: lock: the collector's callback runs inside whatever
        #: allocated, this tracer's own critical sections among them.
        #: Moved into the ring by the next section that takes the lock
        self._timed: deque = deque()
        #: spans ever added: a capture's drops are what it added beyond
        #: the ring's capacity
        self._added = 0
        #: set while a profiler capture is open (capture_begin): the
        #: factory of the annotation a work span holds in the trace
        self._annotation = None
        self._capture_from = (0, 0)  # (epoch us, _added) at its start

    @property
    def sample_rate(self) -> float:
        return self._sample_rate

    @sample_rate.setter
    def sample_rate(self, rate: float) -> None:
        # cached decisions embed the old rate — drop them with it
        self._sample_rate = float(rate)
        self._decision_cache.clear()

    # -------------------------------------------------------- configuration

    @property
    def capacity(self) -> int:
        """Ring capacity (the /healthz occupancy denominator)."""
        return self._capacity

    def set_capacity(self, capacity: int) -> None:
        if capacity == self._capacity:
            return
        with self._lock:
            self._capacity = capacity
            self._spans = deque(self._spans, maxlen=capacity)

    # ------------------------------------------------------------- sampling

    def sampled(self, txid) -> bool:
        """Deterministic per-txid decision (crc32 of the txid repr —
        stable across processes, unlike the salted builtin hash), so
        every plane of every DC traces the SAME transactions and a
        sampled txn's tree is complete.  Untagged spans (background
        stages, non-transactional reads) are thinned to ~rate by
        hashing a call counter: at partial rates they would otherwise
        record on EVERY call and a hot untagged path (e.g. device-
        served value reads) would evict the sampled transactions' trees
        from the ring; hashing (vs a plain modulo) keeps a periodic
        call pattern from phase-locking one call site out of the ring
        entirely."""
        rate = self.sample_rate
        if rate <= 0.0:
            return False
        if rate >= 1.0:
            return True
        if txid is None:
            n = next(self._untagged_seq)
            return (zlib.crc32(n.to_bytes(8, "little")) % 10_000
                    < rate * 10_000)
        cache = self._decision_cache
        hit = cache.get(txid)
        if hit is None:
            hit = txid_decision(txid, rate)
            if len(cache) >= 8192:  # txids are transient; drop en masse
                cache.clear()
            cache[txid] = hit
        return hit

    def adopt(self, txid, decision: bool) -> None:
        """Seed the decision cache with the ORIGIN DC's sampling
        decision for a replicated txn (computed from the wire trace
        header's carried sample rate, ISSUE 7) so the remote halves of
        a sampled txn's tree record even when the local rate differs.
        Only consulted at partial local rates: rate 0 stays fully off
        (the operator turned tracing off) and rate 1 already records
        everything — both short-circuit before the cache."""
        cache = self._decision_cache
        if len(cache) >= 8192:
            cache.clear()
        cache[txid] = bool(decision)

    def adopt_from_wire(self, hdr, txns) -> None:
        """Replay the ORIGIN's deterministic sampling decisions from a
        wire trace header ``(sample permille, ship wall µs)`` over a
        frame's txns — the ONE receive-side adoption rule
        (interdc/dc.py and cluster/federation.py both route here).

        Skip rules: no header means no origin decision to replay; a
        permille of 0 means the origin wasn't tracing, so there is no
        origin decision either — seeding False would silently override
        THIS DC's own partial-rate sampling for that origin's whole
        stream.  And only partial local rates consult the cache at
        all (0 stays off, 1 records everything), so the crc32 loop is
        skipped outside that regime.  The permille is clamped to 1000:
        the decode layer rejects out-of-range values from the wire,
        but in-process senders are not the only callers."""
        if hdr is None or hdr[0] <= 0 \
                or not 0.0 < self.sample_rate < 1.0:
            return
        rate = min(hdr[0], 1000) / 1000.0
        for txn in txns:
            txid = (getattr(txn.records[-1], "txid", None)
                    if txn.records else None)
            if txid is not None:
                self.adopt(txid, txid_decision(txid, rate))

    # ------------------------------------------------------------ recording

    def _records(self, txid) -> bool:
        """One site's decision: everything inside a capture, everything
        under a span that is already being recorded (the outermost site
        decided for the chain), nothing untagged under a request
        that does not record, else the sampling rule."""
        if self._annotation is not None or getattr(_tls, "stack", None):
            return True
        if txid is None and getattr(_tls, "declined", 0):
            return False  # its request's root decided (_Declined)
        return self.sampled(txid)

    def span(self, name: str, cat: str = "host", txid=None, **args):
        """Context manager timing the enclosed block, in which the
        thread runs; no-op (shared null object) when the txid is
        unsampled or tracing is off."""
        if not self._records(txid):
            return _NULL
        return _LiveSpan(self, name, cat, txid, args)

    def wait_span(self, name: str, cat: str = "host", txid=None,
                  **args):
        """:meth:`span` for a block in which the thread sleeps: on a
        lock, a condition, or the device."""
        if not self._records(txid):
            return _NULL
        return _LiveSpan(self, name, cat, txid, args, "wait")

    def root(self, name: str, cat: str, *request_id, **args):
        """:meth:`span` for one served request: ``request_id`` (the
        wire server's connection and message serials) becomes the
        span's txid and the ``req`` of everything recorded under it.
        Thinned like an untagged span, and the id is only built when
        the request records."""
        if not self._records(None):
            return _DECLINED
        return _LiveSpan(self, name, cat, ("req",) + request_id, args,
                         "root")

    def instant(self, name: str, cat: str = "host", txid=None,
                **args) -> None:
        """Zero-duration span — a point event on the trace timeline
        (device stage, txn abort); same sampling rule as :meth:`span`."""
        if not self._records(txid):
            return
        stack = getattr(_tls, "stack", None)
        top = stack[-1] if stack else None
        if top is not None and txid is None:
            txid = top.txid
        self._add(Span(
            next(_SPAN_IDS), top.span_id if top is not None else None,
            name, cat, txid, time.time_ns() // 1000, 0,
            threading.get_ident(), args,
            req=top.req if top is not None else None))

    def current(self):
        """The calling thread's innermost OPEN span, or None.  Only
        call sites that passed the sampling decision push onto the
        stack (unsampled sites get the shared null context), so a
        non-None result means "this call chain is being traced" — the
        hook the kernel-span layer (obs/prof.py) uses to decide whether
        to attach a kernel child-span."""
        stack = getattr(_tls, "stack", None)
        return stack[-1] if stack else None

    def request_id(self):
        """The id of the request the calling thread is serving, when
        that request is being recorded; else None."""
        stack = getattr(_tls, "stack", None)
        return stack[-1].req if stack else None

    def stamp(self):
        """The start of a span that another thread will end
        (:meth:`close_stamp`): the time, and the calling thread's
        place in its request's tree.  None when the call chain is not
        being recorded."""
        stack = getattr(_tls, "stack", None)
        if stack:
            top = stack[-1]
            return (time.time_ns() // 1000, top.span_id, top.req,
                    threading.get_ident())
        if self._annotation is None:
            return None
        return (time.time_ns() // 1000, None, None,
                threading.get_ident())

    def close_stamp(self, stamp, name: str, cat: str, txid=None,
                    kind: str = "wait", **args) -> None:
        """Record the span ``stamp`` began, ending now, on the thread
        and under the parent that took the stamp."""
        start_us, parent_id, req, tid = stamp
        self.record_span(name, cat, txid, start_us,
                         time.time_ns() // 1000 - start_us, parent_id,
                         kind, req, tid, **args)

    def record_span(self, name: str, cat: str, txid, start_us: int,
                    dur_us: int, parent_id: Optional[int] = None,
                    kind: str = "work", req=None,
                    tid: Optional[int] = None, **args) -> None:
        """Record an externally timed, already-finished span: one that
        starts on one thread and ends on another (a staged read's wait
        for its drain) or is timed outside Python (the native planes).
        ``tid`` is the thread it belongs to when that is not the
        caller's.  No sampling check: callers gate on :meth:`current`
        or on a stamp they took when the span began."""
        self._add(Span(
            next(_SPAN_IDS), parent_id, name, cat, txid, int(start_us),
            int(dur_us), tid if tid is not None else
            threading.get_ident(), args, kind, req))

    def open_timed(self, name: str):
        """The start of a work span that a callback times on the
        calling thread (the collector's pause, obs/host.py), when the
        thread's call chain records or a capture is open; else None.
        It holds a stamp (parent: the thread's innermost span) and,
        inside a capture, an annotation of ``name`` nested in the
        thread's own: the thread's stack of open spans is not touched,
        so a callback that fires inside the tracer's own bookkeeping
        leaves it whole.  The span takes its parent's txid, as an
        opened span does."""
        stamp = self.stamp()
        if stamp is None:
            return None
        top = self.current()
        ann, factory = None, self._annotation
        if factory is not None:
            ann = factory(name)
            ann.__enter__()
        return stamp, top.txid if top is not None else None, ann

    def close_timed(self, opened, name: str, cat: str, **args) -> None:
        """Record the span :meth:`open_timed` began, ending now.  Takes
        no lock (``_timed``)."""
        stamp, txid, ann = opened
        if ann is not None:
            ann.__exit__(None, None, None)
        start_us, parent_id, req, tid = stamp
        self._timed.append(Span(
            next(_SPAN_IDS), parent_id, name, cat, txid, start_us,
            time.time_ns() // 1000 - start_us, tid, args, "work", req))

    def _take_timed_locked(self) -> None:
        timed = self._timed
        while timed:
            self._spans.append(timed.popleft())
            self._added += 1

    def _add(self, span: Span) -> None:
        with self._lock:
            self._take_timed_locked()
            self._spans.append(span)
            self._added += 1

    # -------------------------------------------------------------- capture

    @property
    def capturing(self) -> bool:
        return self._annotation is not None

    def capture_begin(self, annotation) -> None:
        """A profiler capture opened (obs.prof.start): record every
        span from here, and hold ``annotation(name)`` (a
        ``jax.profiler.TraceAnnotation``) around each thread's
        innermost work span."""
        with self._lock:
            self._capture_from = (time.time_ns() // 1000, self._added)
        self._annotation = annotation

    def capture_end(self) -> Dict[str, Any]:
        """The capture closed: its bounds, the spans it added that the
        ring still holds, and how many the ring dropped.  Cheap (one
        copy under the lock); :func:`summarize` reduces it later, off
        the serving window."""
        self._annotation = None
        t0_us, added0 = self._capture_from
        with self._lock:
            self._take_timed_locked()
            added = self._added - added0
            kept = min(added, len(self._spans))
            spans = list(itertools.islice(
                self._spans, len(self._spans) - kept, None))
        return {"t0_us": t0_us, "t1_us": time.time_ns() // 1000,
                "spans": spans, "dropped": added - kept}

    # -------------------------------------------------------------- queries

    def spans(self, txid=None, name: Optional[str] = None,
              cat: Optional[str] = None) -> List[Span]:
        """Finished spans, oldest first, filtered by any of
        txid/name/cat (the in-process query surface tests assert on)."""
        with self._lock:
            self._take_timed_locked()
            out = list(self._spans)
        if txid is not None:
            out = [s for s in out if s.txid == txid]
        if name is not None:
            out = [s for s in out if s.name == name]
        if cat is not None:
            out = [s for s in out if s.cat == cat]
        return out

    def tree(self, txid) -> List[dict]:
        """The txn's span tree: ``[{"span": Span, "children": [...]}]``
        roots in start order.  Parent links only bind within a thread's
        nesting; cross-thread/plane spans of the txn surface as
        additional roots — the txid is the correlator."""
        spans = self.spans(txid=txid)
        nodes = {s.span_id: {"span": s, "children": []} for s in spans}
        roots = []
        for s in spans:
            parent = nodes.get(s.parent_id)
            if parent is not None:
                parent["children"].append(nodes[s.span_id])
            else:
                roots.append(nodes[s.span_id])
        return roots

    def planes(self, txid) -> set:
        """Categories the txn's spans cover — the smoke test's
        "crossed coordinator → log → device → interdc" assertion."""
        return {s.cat for s in self.spans(txid=txid)}

    def clear(self) -> None:
        with self._lock:
            self._timed.clear()
            self._spans.clear()

    def __len__(self) -> int:
        with self._lock:
            self._take_timed_locked()
            return len(self._spans)

    # --------------------------------------------------------------- export

    def export_chrome(self, txid=None) -> Dict[str, Any]:
        """Chrome trace_event object (``{"traceEvents": [...]}``) for
        the whole ring or one txn — load in Perfetto / chrome://tracing
        next to a JAX profiler capture of the same window."""
        return {
            "traceEvents": [s.to_trace_event()
                            for s in self.spans(txid=txid)],
            "displayTimeUnit": "ms",
        }

    def export_chrome_json(self, txid=None) -> str:
        return json.dumps(self.export_chrome(txid=txid))

    def save(self, path: str, txid=None) -> str:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            f.write(self.export_chrome_json(txid=txid))
        return path


#: process-wide tracer (all DCs share it, like stats.registry)
tracer = Tracer()


def _merge(intervals: list) -> list:
    """Sorted, disjoint ``(start, end)`` covering the same points."""
    out: list = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        elif end > start:
            out.append((start, end))
    return out


def _minus(keep: list, cut: list) -> list:
    """``keep`` less ``cut``, both merged."""
    out = []
    for start, end in keep:
        for c0, c1 in cut:
            if c1 <= start or c0 >= end:
                continue
            if c0 > start:
                out.append((start, c0))
            start = max(start, c1)
            if start >= end:
                break
        if start < end:
            out.append((start, end))
    return out


def _length(intervals: list) -> int:
    return sum(end - start for start, end in intervals)


def summarize(spans: List[Span], t0_us: int, t1_us: int,
              dropped: int = 0) -> Dict[str, Any]:
    """Reduce the spans of one capture ``[t0_us, t1_us]``.

    ``spans``: per name its category and kind, the count, total, self
    time (duration less the part its children cover, a child being any
    span that names it as parent, on whatever thread) and p95
    (nearest rank).  ``requests``: per message kind the roots' count
    and total.  ``host_busy_s``: the length of the capture in which at
    least one thread was inside a work span and outside every wait
    span — the time the one Python process had something to run."""
    children: Dict[int, list] = {}
    for s in spans:
        if s.parent_id is not None:
            children.setdefault(s.parent_id, []).append(s)
    by_name: Dict[str, dict] = {}
    durs: Dict[str, list] = {}
    requests: Dict[str, dict] = {}
    work: Dict[int, list] = {}
    waits: Dict[int, list] = {}
    for s in spans:
        end = s.start_us + s.dur_us
        covered = _length(_merge(
            [(max(c.start_us, s.start_us),
              min(c.start_us + c.dur_us, end))
             for c in children.get(s.span_id, ())]))
        row = by_name.setdefault(s.name, {
            "cat": s.cat, "kind": s.kind, "count": 0, "total_s": 0.0,
            "self_s": 0.0})
        row["count"] += 1
        row["total_s"] += s.dur_us / 1e6
        row["self_s"] += (s.dur_us - covered) / 1e6
        durs.setdefault(s.name, []).append(s.dur_us)
        if s.kind == "root":
            r = requests.setdefault(str(s.args.get("kind")),
                                    {"count": 0, "total_s": 0.0})
            r["count"] += 1
            r["total_s"] += s.dur_us / 1e6
        elif s.dur_us:
            (waits if s.kind == "wait" else work).setdefault(
                s.tid, []).append((max(s.start_us, t0_us),
                                   min(end, t1_us)))
    for name, row in by_name.items():
        d = sorted(durs[name])
        row["p95_s"] = d[max(0, -(-95 * len(d) // 100) - 1)] / 1e6
    busy: list = []
    for tid, intervals in work.items():
        busy += _minus(_merge(intervals), _merge(waits.get(tid, [])))
    return {
        "length_s": (t1_us - t0_us) / 1e6,
        "span_count": len(spans),
        "dropped": dropped,
        "spans": by_name,
        "requests": requests,
        "requests_answered": sum(r["count"] for r in requests.values()),
        "host_busy_s": _length(_merge(busy)) / 1e6,
    }


def traced(name: str, cat: str):
    """Decorator spanning a coordinator-shaped method (``self, tx,
    ...``) with the transaction's txid — the instrumentation idiom
    tools/trace_lint.py enforces on public txn entry points."""
    import functools

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(self, tx, *args, **kwargs):
            with tracer.span(name, cat, txid=tx.txid):
                return fn(self, tx, *args, **kwargs)
        return wrapper
    return deco
