"""One span path from the wire to the device (PR 25): a served request
is one tree under one request id, an unrecorded request costs a null
context per site, a capture lands the work spans in the profiler's own
trace and never the waits or the root, no wrapped kernel call fetches
from the device in order to time itself, and the capture summary's
arithmetic."""

import logging
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from antidote_tpu.api import AntidoteTPU
from antidote_tpu.config import Config
from antidote_tpu.obs import prof
from antidote_tpu.obs.events import recorder
from antidote_tpu.obs.prof import kernel_span, profiler
from antidote_tpu.obs.spans import (
    _DECLINED,
    _NULL,
    Span,
    Tracer,
    summarize,
    tracer,
)
from antidote_tpu.pb.client import PbClient
from antidote_tpu.pb.server import PbServer


@pytest.fixture(autouse=True)
def _isolate_obs_globals(tmp_path):
    saved = (tracer.sample_rate, recorder.dump_dir)
    tracer.clear()
    profiler.reset()
    recorder.dump_dir = str(tmp_path / "flightrec")
    yield
    tracer.sample_rate, recorder.dump_dir = saved
    tracer.clear()
    profiler.reset()


COUNTERS = [(f"c{i}", "counter_pn", "b") for i in range(6)]
SETS = [(f"s{i}", "set_aw", "b") for i in range(4)]
UPDATES = ([(k, "increment", i + 1) for i, k in enumerate(COUNTERS)]
           + [(k, "add", f"e{i}") for i, k in enumerate(SETS)])


@pytest.fixture
def served(tmp_path):
    """One partition over the wire whose value cache holds nothing, so
    that a read asks the device."""
    db = AntidoteTPU(config=Config(n_partitions=1),
                     data_dir=str(tmp_path / "data"))
    for pm in db.node.partitions:
        pm._val_cache_cap = 0
        pm.seed_cache_on_first_publish = False
    srv = PbServer(db, port=0).start()
    cl = PbClient(port=srv.port)
    try:
        yield db, cl
    finally:
        cl.close()
        srv.stop()
        db.close()


def _tree_of(root):
    """{span_id: node} of every span recorded under ``root``'s request
    id, children linked by parent_id."""
    spans = [s for s in tracer.spans() if s.req == root.req]
    nodes = {s.span_id: {"span": s, "children": []} for s in spans}
    for s in spans:
        if s.parent_id is not None:
            nodes[s.parent_id]["children"].append(nodes[s.span_id])
    return nodes


def _names_below(node):
    out = set()
    for c in node["children"]:
        out.add(c["span"].name)
        out |= _names_below(c)
    return out


def _self_us(node):
    s = node["span"]
    covered = sum(c["span"].dur_us for c in node["children"])
    return s.dur_us - covered


def _request(kind):
    # the handler records the root after the answer is on the wire
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        roots = [s for s in tracer.spans(name="pb_request")
                 if s.args.get("kind") == kind]
        if roots:
            return roots[-1]
        time.sleep(0.01)
    raise AssertionError(f"no pb_request span of kind {kind}")


def test_a_read_over_the_wire_is_one_tree_under_one_request_id(served):
    _db, cl = served
    ct = cl.update_objects_static(None, UPDATES)
    tracer.clear()
    tracer.sample_rate = 1.0
    values, _snap = cl.read_objects_static(ct, COUNTERS + SETS)
    assert values[:6] == [1, 2, 3, 4, 5, 6]
    root = _request("ApbStaticReadObjects")
    assert root.kind == "root" and root.parent_id is None
    assert root.txid == root.req and root.req[0] == "req"
    assert root.args["bytes_in"] > 0 and root.args["bytes_out"] > 0
    nodes = _tree_of(root)
    # one tree: every span of the request hangs off the root
    orphans = [n["span"].name for n in nodes.values()
               if n["span"].parent_id is None and n["span"] is not root]
    assert orphans == []
    assert all(n["span"].parent_id in nodes for n in nodes.values()
               if n["span"] is not root)
    # a read carries no transaction: the request id is every span's txid
    assert {n["span"].txid for n in nodes.values()} == {root.req}
    below = _names_below(nodes[root.span_id])
    for name in ("pb_decode", "api_static_read", "txn_snapshot",
                 "read_serve_queue_wait", "read_serve_drain",
                 "read_serve_classify", "read_serve_fold",
                 "device_prepare", "device_dispatch", "device_fetch",
                 "pb_encode_send"):
        assert name in below, (name, sorted(below))
    kinds = {n["span"].name: n["span"].kind for n in nodes.values()}
    assert kinds["device_fetch"] == "wait" == kinds["read_serve_queue_wait"]
    assert kinds["device_dispatch"] == "work" == kinds["pb_decode"]
    # from pb_request down to the device fetch, by parent links alone
    fetch = next(n["span"] for n in nodes.values()
                 if n["span"].name == "device_fetch")
    chain, at = [], fetch
    while at.parent_id is not None:
        at = nodes[at.parent_id]["span"]
        chain.append(at.name)
    assert chain[-1] == "pb_request" and "api_static_read" in chain
    # the self times of the whole tree add up to the root's duration
    assert sum(_self_us(n) for n in nodes.values()) == root.dur_us
    assert all(_self_us(n) >= 0 for n in nodes.values())


def test_an_update_over_the_wire_keeps_its_txid_under_the_request(served):
    _db, cl = served
    cl.update_objects_static(None, UPDATES)
    tracer.clear()
    tracer.sample_rate = 1.0
    cl.update_objects_static(None, UPDATES)
    root = _request("ApbStaticUpdateObjects")
    nodes = _tree_of(root)
    below = _names_below(nodes[root.span_id])
    for name in ("pb_decode", "api_static_update", "txn_snapshot",
                 "txn_update", "txn_commit", "single_commit",
                 "log_append_commit", "pb_encode_send"):
        assert name in below, (name, sorted(below))
    # the transaction's own spans keep the transaction's id (the log
    # and the other DCs know it by that) and the request's ``req``
    commit = next(n["span"] for n in nodes.values()
                  if n["span"].name == "txn_commit")
    assert commit.txid != root.req and commit.req == root.req
    assert sum(_self_us(n) for n in nodes.values()) == root.dur_us


def test_an_unrecorded_request_meets_null_contexts_only(served):
    """Part E: outside a capture, with the request unsampled, a span
    site is the sampling check and the shared null context."""
    _db, cl = served
    tracer.sample_rate = 0.0
    ct = cl.update_objects_static(None, UPDATES)
    cl.read_objects_static(ct, COUNTERS + SETS)
    time.sleep(0.05)
    assert len(tracer) == 0
    assert tracer.span("api_static_read", "api", keys=3) is _NULL
    assert tracer.wait_span("pm_lock_wait", "manager") is _NULL
    assert tracer.root("pb_request", "wire", 1, 2) is _DECLINED
    assert tracer.stamp() is None and tracer.request_id() is None
    # a request records whole or not at all: under a root that
    # declined, no untagged site asks the sampler again
    tracer.sample_rate = 0.5
    declined = 0
    for i in range(200):
        with tracer.root("pb_request", "wire", 1, i) as root:
            if root is None:
                declined += 1
                assert tracer.span("pb_decode", "wire") is _NULL
                assert tracer.wait_span("w", "manager") is _NULL
                tracer.instant("ingest_flush", "device")
    assert 40 < declined < 160
    assert {s.name for s in tracer.spans()} == {"pb_request"}
    assert len(tracer) == 200 - declined
    assert tracer.span("after", "host", txid="t-after") is not None
    tracer.clear()
    tracer.sample_rate = 0.0

    # test_obs_prof's method, on the new kinds of site: bounded wall
    # time against the bare block, generous enough for a noisy core
    def bare():
        return 1 + 1

    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        with tracer.root("pb_request", "wire", 1, 2), \
                tracer.span("pb_decode", "wire"), \
                tracer.wait_span("pm_lock_wait", "manager"):
            bare()
    dt_sites = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(n):
        bare()
    dt_bare = time.perf_counter() - t0
    assert dt_sites < dt_bare * 3 + 0.05, (dt_sites, dt_bare)
    assert len(tracer) == 0


def test_an_uncontended_partition_lock_opens_no_span(served):
    db, _cl = served
    tracer.sample_rate = 1.0
    pm = db.node.partitions[0]
    tracer.clear()   # what opening the node recorded, if sampled
    with tracer.span("probe", "host"):
        with pm._locked:
            pm._wait_device_quiesce()
    assert [s.name for s in tracer.spans()] == ["probe"]


def _host_events(log_dir):
    from benchmark import trace

    data = jax.profiler.ProfileData.from_file(trace.xplane_of(log_dir))
    out = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                out.append((line.name, e.name, e.duration_ns))
    return out


def test_a_capture_holds_the_work_spans_and_never_a_wait_or_the_root(
        tmp_path):
    tracer.sample_rate = 0.0   # the capture alone makes these record
    with prof.profile(str(tmp_path / "cap")):
        assert tracer.capturing
        with tracer.root("pb_request", "wire", 9, 1):
            with tracer.span("stage_outer", "host"):
                with tracer.span("stage_leaf", "host"):
                    time.sleep(0.02)
                with tracer.wait_span("asleep_wait", "host"):
                    time.sleep(0.01)
    assert not tracer.capturing
    events = _host_events(str(tmp_path / "cap"))
    names = {name for _line, name, _d in events}
    assert "stage_leaf" in names and "stage_outer" in names
    assert "asleep_wait" not in names and "pb_request" not in names
    # the annotated thread's line is one the benchmark's gap
    # attribution reads (benchmark/trace.py: names starting "python")
    assert all(line.startswith("python") for line, name, _d in events
               if name in ("stage_leaf", "stage_outer"))
    # a leaf's annotation is its span, on the profiler's clock
    (leaf,) = tracer.spans(name="stage_leaf")
    (ann_ns,) = [d for _l, name, d in events if name == "stage_leaf"]
    assert ann_ns / 1000 == pytest.approx(leaf.dur_us, abs=2000)
    # a span with children is annotated for its self time only, in
    # pieces: the stage and the JAX call beneath it never nest deeper
    (outer,) = tracer.spans(name="stage_outer")
    pieces = [d for _l, name, d in events if name == "stage_outer"]
    (asleep,) = tracer.spans(name="asleep_wait")
    assert len(pieces) >= 2
    self_us = outer.dur_us - leaf.dur_us - asleep.dur_us
    assert sum(pieces) / 1000 == pytest.approx(self_us, abs=1000)
    cap = prof.last_capture()
    assert cap["dropped"] == 0 and cap["requests_answered"] == 1
    assert cap["spans"]["asleep_wait"]["kind"] == "wait"
    # busy: the root and the wait are not, the two stages are
    assert cap["host_busy_s"] == pytest.approx(
        (outer.dur_us - asleep.dur_us) / 1e6, abs=0.001)


def test_no_wrapped_kernel_call_indexes_its_result_on_the_host(
        tmp_path, caplog):
    """Part C: the tracer's completion fetch dispatched
    jit(dynamic_slice), jit(squeeze) and jit(convert_element_type)
    after every wrapped call inside a capture; nothing does now."""

    @kernel_span("t", name="odd_shape_probe")
    @jax.jit
    def k(x):
        return x * 3

    x = jnp.ones((13, 7, 3), jnp.int32)   # a shape no other test has
    k(x)                                  # compile outside the capture
    indexers = ("dynamic_slice", "squeeze", "convert_element_type")

    def compiled():
        return [r.getMessage() for r in caplog.records
                if "Compiling" in r.getMessage()
                and any(n in r.getMessage() for n in indexers)]

    tracer.sample_rate = 1.0
    with caplog.at_level(logging.WARNING), jax.log_compiles():
        with prof.profile(str(tmp_path / "cap")):
            with tracer.span("device_read", "device", txid="tx-c"):
                out = k(x)
        assert compiled() == []
        (kspan,) = tracer.spans(name="kernel:odd_shape_probe")
        assert kspan.args["timing"] == "dispatch"
        # the control: the old fetch, done by hand, is seen
        np.asarray(out[(0, 0, 0)])
        assert compiled() != []
    snap = profiler.snapshot()["kernels"]["odd_shape_probe"]
    assert snap["calls"] == 2 and "completions" not in snap


def _span(i, parent, name, start, dur, tid=1, kind="work", cat="c",
          args=None):
    return Span(i, parent, name, cat, None, start, dur, tid, args or {},
                kind, ("req", 1, 1))


def test_summarize_self_time_union_and_drops():
    spans = [
        _span(1, None, "pb_request", 0, 1000, kind="root", cat="wire",
              args={"kind": "ApbStaticReadObjects"}),
        _span(2, 1, "decode", 0, 100, cat="wire"),
        _span(3, 1, "api", 100, 800, cat="api"),
        # two children of "api" that overlap: covered once (600..700)
        _span(4, 3, "fold", 200, 500),
        _span(5, 3, "queue_wait", 600, 200, kind="wait"),
        # a second thread: work 500..1500, asleep 900..1400 inside it
        _span(6, None, "flush", 500, 1000, tid=2),
        _span(7, 6, "fetch", 900, 500, tid=2, kind="wait"),
        # a child on another thread than its parent still covers it
        _span(8, 2, "helper", 50, 25, tid=3),
    ]
    cap = summarize(spans, 0, 2000, dropped=3)
    rows = cap["spans"]
    assert cap["length_s"] == pytest.approx(0.002)
    assert cap["span_count"] == 8 and cap["dropped"] == 3
    assert rows["pb_request"]["self_s"] == pytest.approx(100e-6)
    assert rows["decode"]["self_s"] == pytest.approx(75e-6)
    assert rows["api"]["total_s"] == pytest.approx(800e-6)
    assert rows["api"]["self_s"] == pytest.approx(200e-6)  # 800 - [200,800)
    assert rows["flush"]["self_s"] == pytest.approx(500e-6)
    assert rows["queue_wait"]["kind"] == "wait"
    assert rows["api"]["cat"] == "api" and rows["api"]["count"] == 1
    assert cap["requests"] == {"ApbStaticReadObjects": {
        "count": 1, "total_s": pytest.approx(1000e-6)}}
    assert cap["requests_answered"] == 1
    # thread 1 busy 0..600 (decode, api, fold; the wait from 600 cuts
    # api and fold short) and 800..900 (api after the wait); thread 2
    # busy 500..900 and 1400..1500; thread 3 inside thread 1's.  The
    # root is nobody's work.  Union: 0..900 and 1400..1500
    assert cap["host_busy_s"] == pytest.approx(1000e-6)
    # p95 by nearest rank
    many = [_span(100 + i, None, "x", 0, i + 1) for i in range(100)]
    assert summarize(many, 0, 200)["spans"]["x"]["p95_s"] == \
        pytest.approx(95e-6)
    one = summarize(many[:1], 0, 200)["spans"]["x"]
    assert one["p95_s"] == pytest.approx(1e-6)


def test_a_capture_counts_what_the_ring_dropped(tmp_path):
    t = Tracer(capacity=8, sample_rate=0.0)
    t.capture_begin(lambda name: jax.profiler.TraceAnnotation(name))
    for _ in range(20):
        with t.span("s", "host"):
            pass
    raw = t.capture_end()
    assert raw["dropped"] == 12 and len(raw["spans"]) == 8
    assert not t.capturing and t.span("s", "host") is _NULL
    # spans from before the capture are not the capture's
    t2 = Tracer(capacity=8, sample_rate=1.0)
    for _ in range(5):
        with t2.span("old", "host"):
            pass
    t2.capture_begin(lambda name: jax.profiler.TraceAnnotation(name))
    with t2.span("new", "host"):
        pass
    raw = t2.capture_end()
    assert [s.name for s in raw["spans"]] == ["new"]
    assert raw["dropped"] == 0


def test_a_stamp_ends_on_another_thread_under_its_own_parent():
    import threading

    tracer.sample_rate = 1.0
    with tracer.root("pb_request", "wire", 3, 4) as root:
        with tracer.span("api_static_read", "api") as api:
            stamp = tracer.stamp()
            t = threading.Thread(target=lambda: tracer.close_stamp(
                stamp, "read_serve_queue_wait", "serve", txid=root.txid))
            t.start()
            t.join()
    (w,) = tracer.spans(name="read_serve_queue_wait")
    assert w.kind == "wait" and w.parent_id == api.span_id
    assert w.req == ("req", 3, 4) and w.tid == threading.get_ident()
