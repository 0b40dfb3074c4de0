"""The plain reference with its control, and the trace reduction on a
small trace recorded on a v5e."""

import os

import pytest

from benchmark import reference, trace
from benchmark.traffic import Keyspace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
RECORDED = os.path.join(ROOT, "benchmark", "data", "v5e_micro.xplane.pb")
KS = Keyspace.of(2, 64, {"counter_pn": 3, "set_aw": 1})


def history():
    load = KS.load_values(1)
    return reference.PlainHistory(KS, load), load.incs


def test_a_read_sees_every_write_at_or_before_its_snapshot():
    h, incs = history()
    key = 4  # row 2: a counter
    base = int(incs[key])
    h.write(key, 100, "increment", 5)
    h.write(key, 200, "decrement", 2)
    assert h.at(key, 99) == base
    assert h.at(key, 100) == base + 5
    assert h.at(key, 199) == base + 5
    assert h.at(key, 200) == h.at(key) == base + 3


def test_a_set_reads_as_a_sorted_list():
    h, _ = history()
    key = 3 * KS.n_partitions  # row 3: a set
    assert KS.type_of(key) == "set_aw"
    loaded = h.at(key)
    h.write(key, 10, "add", b"e5")
    h.write(key, 10, "remove", loaded[0])  # the same transaction
    want = sorted((set(loaded) | {b"e5"}) - {loaded[0]})
    assert h.at(key, 10) == want and h.at(key, 9) == loaded


def test_writes_fed_out_of_commit_order_are_refused():
    h, _ = history()
    h.write(0, 50, "increment", 1)
    with pytest.raises(ValueError, match="out of commit order"):
        h.write(0, 40, "increment", 1)


def update(client, commit_time, updates, clock_sent=None):
    return {"client": client, "kind": "update_only_txn", "ok": True,
            "read_keys": [], "updates": updates, "values": None,
            "snapshot_time": None, "commit_time": commit_time,
            "clock_sent": clock_sent, "aborts": 0}


def read(client, snapshot_time, keys, values, clock_sent=None):
    return {"client": client, "kind": "read_only_txn", "ok": True,
            "read_keys": keys, "updates": [], "values": values,
            "snapshot_time": snapshot_time, "commit_time": None,
            "clock_sent": clock_sent, "aborts": 0}


def test_many_writers_of_one_key_are_ordered_by_commit_time():
    h, incs = history()
    elem = b"e5"
    set_key = 3 * KS.n_partitions
    loaded = set(h.at(set_key))
    # three clients' records, each in its own order, interleaved in time
    recs = [update(0, 100, [(0, "increment", 1)]),
            update(0, 400, [(0, "increment", 8), (set_key, "add", elem)]),
            update(1, 300, [(0, "decrement", 2)]),
            update(2, 200, [(0, "increment", 4)]),
            update(2, 500, [(set_key, "remove", elem)]),
            dict(update(1, 250, [(0, "increment", 99)]), ok=False)]
    reference.feed(h, recs)
    base = int(incs[0])
    assert [h.at(0, t) for t in (99, 100, 250, 300, 400)] \
        == [base, base + 1, base + 5, base + 3, base + 11]
    assert h.at(set_key, 450) == sorted(loaded | {elem})
    assert h.at(set_key, 500) == sorted(loaded - {elem})


def test_a_snapshot_behind_the_sessions_clock_is_counted():
    recs = [read(0, 150, [0], [0], clock_sent=None),
            read(0, 150, [0], [0], clock_sent=150),
            read(0, 149, [0], [0], clock_sent=150),       # behind
            update(1, 300, [(0, "increment", 1)], clock_sent=250),
            update(1, 240, [(0, "increment", 1)], clock_sent=250),  # behind
            dict(read(2, 1, [0], [0], clock_sent=150), ok=False)]
    assert reference.behind_session(recs) == (4, 2)


def records_of_one_writer(h):
    recs = [update(0, t, [(0, "increment", i + 1)],
                   clock_sent=t - 100 if i else None)
            for i, t in enumerate((100, 200, 300))]
    reference.feed(h, recs)
    return recs + [read(1, 250, [0, 1], [h.at(0, 250), h.at(1, 250)])]


def test_sound_answers_compare_equal_and_the_control_does_not():
    h, _ = history()
    recs = records_of_one_writer(h)
    assert reference.wrong_reads(h, recs)[:2] == (2, 0)
    control = reference.control_numbers(h, recs, {"keys": [0, 1]})
    # key 0 was written before the snapshot: the stale store shows one
    # write less; key 1 never was: nothing to be late with
    # and the writer's third transaction is answered at the clock sent
    # with its second, behind the clock it sent itself
    assert control == {"reads_wrong": 1, "acks_unreadable": 1,
                       "snapshots_behind_session": 1}
    assert reference.behind_session(recs) == (2, 0)


def test_an_altered_answer_is_counted():
    h, _ = history()
    recs = records_of_one_writer(h)
    recs[-1]["values"][0] += 1
    compared, wrong, first = reference.wrong_reads(h, recs)
    assert (compared, wrong) == (2, 1) and "key 0" in first[0]


@pytest.mark.parametrize("numbers,verdict", [
    ([("reads_wrong", 0, "<=", 0), ("device_reads", 3, ">=", 1)], True),
    ([("reads_wrong", 1, "<=", 0), ("device_reads", 3, ">=", 1)], False),
    ([("reads_wrong", 0, "<=", 0), ("device_reads", 0, ">=", 1)], False),
])
def test_every_number_is_held_to_its_limit(numbers, verdict):
    assert reference.judge(numbers) is verdict
    lines, obj = reference.compared_lines(numbers)
    assert len(lines) == len(numbers) == len(obj)
    assert all("limit" in line for line in lines)
    assert obj["reads_wrong"]["limit"] == 0


# ------------------------------------------------------------- the trace


@pytest.mark.parametrize("intervals,total", [
    ([], 0),
    ([(0, 10), (20, 30)], 20),            # apart
    ([(0, 10), (5, 15)], 15),             # overlapping
    ([(0, 100), (10, 20), (30, 40)], 100),  # nested
    ([(0, 10), (10, 20)], 20),            # touching
    ([(5, 15), (0, 10), (12, 13)], 15),   # out of order
])
def test_busy_time_is_the_union_of_intervals(intervals, total):
    assert trace.union_ns(intervals) == total


def test_the_recorded_v5e_trace_reduces_to_the_numbers_read_by_hand():
    out = trace.reduce_xplane(RECORDED)
    # six program runs, 21 operations, 189,406 ns of them (PR 24)
    assert out["busy_s"] == pytest.approx(189406e-9, abs=1e-12)
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["device_planes"] == 1
    names = [n for n, _s in out["breakdown"]["device_ops"]]
    assert names == ["jit__lambda"]
    assert len(out["breakdown"]["idle_gaps"]) <= 10
    idle = sum(s for _n, s in out["breakdown"]["idle_gaps"])
    assert idle == pytest.approx(out["window_s"] - out["busy_s"], rel=1e-6)


def test_a_trace_without_a_device_plane_is_an_error(tmp_path):
    # a capture on the CPU backend holds host planes only; it goes
    # through the same capture() a traced run uses
    import jax.numpy as jnp

    with trace.capture(str(tmp_path), 0.05):
        jnp.arange(8).sum().block_until_ready()
    with pytest.raises(trace.TraceError, match="no device plane"):
        trace.reduce_xplane(trace.xplane_of(str(tmp_path)))


def test_no_trace_file_and_an_unknown_device_are_errors(tmp_path):
    with pytest.raises(trace.TraceError, match="no .xplane.pb"):
        trace.xplane_of(str(tmp_path))
    with pytest.raises(trace.TraceError, match="no peaks"):
        trace.peaks_for("cpu")
    assert trace.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_needed_bytes_from_the_planes_shapes():
    rows = {"counter_pn": {"key_row": 800.0, "op_row": 89.0},
            "set_aw": {"key_row": 2000.0, "op_row": 241.0}}
    got = trace.needed_bytes(rows, {"counter_pn": 10, "set_aw": 2},
                             {"counter_pn": 3})
    assert got == 10 * 800.0 + 2 * 2000.0 + 3 * 89.0
    assert trace.needed_bytes(rows, {}, {}) == 0
