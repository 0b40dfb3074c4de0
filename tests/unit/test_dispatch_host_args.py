"""A dispatch's host arrays ride in as the program's arguments (PR 34):
a capture keeps the padded index vector and an explicit snapshot's
dense row as NumPy, a flush its one packed tensor, and the jitted call
uploads them itself — after the partition lock, and without JAX's
Python path (``jnp.asarray`` of a 64-entry vector cost more than the
program's own dispatch).  What must hold: the same programs (one
abstract value either way), the same answers, and a capture that is
still a snapshot."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from antidote_tpu.clocks import VC
from antidote_tpu.mat import device_plane, ingest
from antidote_tpu.mat.device_plane import DevicePlane
from antidote_tpu.mat.materializer import Payload
from antidote_tpu.obs.prof import profiler
from benchmark import harness
from tests.unit.test_single_key_read import OPS, stage_ops

#: every plane type with a batched fold of its own (``_many_split``);
#: the maps read through these, RGA a document at a time
FLAT = sorted(t for t in OPS if not t.startswith("map_"))
CLOCKS = {"explicit": VC({"dc1": 100}), "latest": None}


@pytest.fixture(scope="module")
def compiles():
    """Programs compiled so far, as the benchmark's
    ``compiles_in_window`` counts them: JAX's own compile events
    (the harness's watcher) plus the kernel spans' first-seen
    signatures."""
    watch = harness.CompileWatch()

    def read():
        return watch.programs, sum(
            k["compile_misses"]
            for k in profiler.snapshot()["kernels"].values())

    return read


def join_warms():
    for t in threading.enumerate():
        if t.name.startswith("warm"):
            t.join(timeout=120)
            assert not t.is_alive(), t.name


def held(plane):
    """What a reader's count does in the server: the state a capture
    holds is not donated to a later flush until the capture has run.
    Here the plane goes on with a copy, so the later flush donates
    that."""
    plane.st = jax.tree_util.tree_map(jnp.copy, plane.st)


@pytest.mark.parametrize("clock", sorted(CLOCKS))
@pytest.mark.parametrize("type_name", FLAT)
def test_a_capture_holds_no_device_array_but_the_state(type_name, clock):
    plane = DevicePlane().planes[type_name]
    want = stage_ops(plane, "k", type_name, OPS[type_name])
    stage_ops(plane, "other", type_name, OPS[type_name][:1])
    plane.flush("test")
    run = plane.read_many_begin(["k"], CLOCKS[clock])
    (fn, args), post = run.split
    st, pad, rv = args
    assert st is plane.st
    assert type(pad) is np.ndarray and pad.dtype == np.int32
    assert pad.shape == (ingest.bucket(1),)
    if clock == "latest":
        # the one cached device array: made once a domain width
        assert rv is plane._inf_rv and isinstance(rv, jax.Array)
    else:
        assert type(rv) is np.ndarray and rv.dtype == np.int64
    assert rv.shape == (plane.domain.d,)
    # the parent's form of the same call: every argument a device array
    parent = post(jax.tree_util.tree_map(
        np.asarray, fn(st, jnp.asarray(pad), jnp.asarray(rv))))
    assert run() == parent == {"k": want}


@pytest.mark.parametrize("type_name", FLAT)
def test_numpy_arguments_meet_the_programs_device_arrays_compiled(
        type_name, compiles):
    plane = DevicePlane().planes[type_name]
    at = VC({"dc1": 100})
    # the warm-up, in the parent's form: the flush's and the read's
    # programs compiled for device-array arguments
    b = ingest.bucket(1)
    pk = np.zeros((b, 2 + ingest.packed_width(plane._row_cols,
                                              plane.domain.d)),
                  dtype=np.int64)
    pk[:, 0] = plane.capacity           # all padding
    assert plane._packed_perm() is not None
    plane.st, _over = ingest.packed_append(plane.st, jnp.asarray(pk))
    for rv in (plane._read_vc_dense(None), plane._read_vc_dense(at)):
        (fn, (st, pad, _rv)), _post = plane._many_split(
            plane.st, [], np.zeros(0, dtype=np.int32),
            np.zeros(b, dtype=np.int32), rv)
        jax.block_until_ready(fn(st, jnp.asarray(pad), jnp.asarray(rv)))
    join_warms()
    before = compiles()
    want = stage_ops(plane, "k", type_name, OPS[type_name])
    plane.flush("test")
    assert plane.read("k", at) == want
    assert plane.read("k", None) == want
    assert compiles() == before


@pytest.mark.parametrize("clock", sorted(CLOCKS))
@pytest.mark.parametrize("type_name", FLAT)
def test_a_capture_run_after_a_later_flush_answers_its_own_snapshot(
        type_name, clock):
    plane = DevicePlane().planes[type_name]
    ops = OPS[type_name]
    first = stage_ops(plane, "k", type_name, ops[:1])
    early = plane.read_many_begin(["k"], CLOCKS[clock])
    _st, pad, rv = early.split[0][1]
    pad0, rv0 = pad.copy(), np.asarray(rv).copy()
    held(plane)
    # later: another key staged and flushed, and captures of both keys
    # at both kinds of clock, which run first
    second = stage_ops(plane, "k2", type_name, ops)
    plane.flush("test")
    for vc in CLOCKS.values():
        late = plane.read_many_begin(["k2", "k"], vc)
        assert late.split[0][1][1] is not pad
        assert late() == {"k": first, "k2": second}
    assert early() == {"k": first}
    assert np.array_equal(pad, pad0) and np.array_equal(np.asarray(rv), rv0)


@pytest.mark.parametrize("clock", ["explicit", "latest"])
def test_a_later_write_of_the_key_is_not_in_the_earlier_capture(clock):
    plane = DevicePlane().planes["counter_pn"]
    at = {"explicit": VC({"dc1": 20}), "latest": None}[clock]
    assert stage_ops(plane, "k", "counter_pn", [("increment", 5)]) == 5
    early = plane.read_many_begin(["k"], at)
    held(plane)
    plane.stage("k", Payload(
        key="k", type_name="counter_pn", effect=7, commit_dc="dc1",
        commit_time=50, snapshot_vc=VC({"dc1": 49}), txid=("t", 50),
        certified=True))
    plane.flush("test")
    assert plane.read("k", None) == 12
    assert plane.read("k", VC({"dc1": 20})) == 5
    assert early() == {"k": 5}


def test_the_packed_flush_hands_its_numpy_tensor_to_the_program(
        monkeypatch):
    seen = []
    real = ingest.packed_append

    def spy(st, packed, *a):
        seen.append(packed)
        return real(st, packed, *a)

    monkeypatch.setattr(device_plane.ingest, "packed_append", spy)
    plane = DevicePlane().planes["counter_pn"]
    want = stage_ops(plane, "k", "counter_pn", OPS["counter_pn"])
    plane.flush("test")
    assert [type(p) for p in seen] == [np.ndarray]
    assert seen[0].dtype == np.int64
    assert plane.read("k", None) == want
