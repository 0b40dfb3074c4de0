"""A configuration's record types and YCSB's scrambled Zipfian, as data:
a YCSB-A-shaped configuration (a record of ten 100-byte registers, one
record a transaction, reads and updates alike, ``ycsb_zipfian`` keys)
added to a tiny tree as files and an entry, and run whole on the CPU;
the reference and its control for records; the generator against the
closed form of the source's constants; the bytes a record's work
needs; and what is refused."""

import collections
import json
import os
import threading

import numpy as np
import pytest

from test_rehearsal import last_line, on_the_cpu  # noqa: F401 — fixture

from benchmark import check_seeds, harness, reference, run, trace
from benchmark.traffic import (
    ZIPF_THETA,
    ZIPF_ZETAN,
    ClientStream,
    Keyspace,
    Mix,
    field_value,
    fnvhash64,
)

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
YCSB = {"kind": "ycsb_zipfian"}
RECORD = {"fields": {"register_lww": 10}, "field_bytes": 100}
#: the tiny cells of this file: a map_go of registers (YCSB-A's
#: record); a map_rr of multi-value registers, whose fields reset
TYPES = {"ycsb": {"map_go": RECORD},
         "ycsb-rr": {"map_rr": {"fields": {"register_mv": 10},
                                "field_bytes": 100}}}


def config_doc(name: str, types: dict, keys_per_partition: int) -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs", "bb1dc.json")) as f:
        doc = json.load(f)
    doc.update(name=name, partitions=2, types=types,
               keys_per_partition=keys_per_partition)
    return doc


def mix_doc(**changes) -> dict:
    doc = {"name": "read50-zipfian", "loop": "closed", "clients": 4,
           "operations": {"read_only_txn": 1, "update_only_txn": 1},
           "num_reads": 1, "num_updates": 1, "key_generator": YCSB,
           "retry_for_s": 10.0, "retry_pause_ms": 20.0}
    doc.update(changes)
    return doc


def add_cell(root: str, config: str, types: dict,
             keys_per_partition: int = 512, **mix_changes) -> str:
    """A configuration file, a mix file and a cell added to the tree
    ``root`` and nothing else edited: the way a later PR adds one."""
    bench_dir = os.path.join(root, "benchmark")
    cfg_file = f"benchmark/configs/{config}.json"
    with open(os.path.join(root, cfg_file), "w") as f:
        json.dump(config_doc(config, types, keys_per_partition), f)
    mix = mix_doc(**mix_changes)
    with open(os.path.join(bench_dir, "traffic", mix["name"] + ".json"),
              "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": config, "source": "YCSB workload A",
                             "file": cfg_file, "reduced": [], "why": "t"})
    cell = f"{config}.{mix['name']}"
    bench["workloads"].append({"name": cell, "config": config,
                               "traffic": mix["name"], "chips": 1,
                               "why": "t"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return cell


# ------------------------------------------------------ the types as data


def test_the_weights_lay_types_on_rows_in_file_order():
    ks = Keyspace.of(4, 64, {"counter_pn": 3, "set_aw": 1})
    assert ks.layout == ("counter_pn",) * 3 + ("set_aw",)
    assert [ks.type_of(r * 4 + 1) for r in range(8)] == \
        ["counter_pn"] * 3 + ["set_aw"] + ["counter_pn"] * 3 + ["set_aw"]
    mixed = Keyspace.of(2, 64, {"set_aw": 2, "map_go": dict(RECORD,
                                                             weight=3)})
    assert mixed.layout == ("set_aw",) * 2 + ("map_go",) * 3
    assert mixed.record("set_aw") is None
    rec = mixed.record("map_go")
    assert rec.field_bytes == 100
    assert rec.fields == tuple((f"field{i}", "register_lww")
                               for i in range(10))


def test_a_record_is_loaded_whole_and_updated_one_field_at_a_time(
        tmp_path):
    ks = Keyspace.of(2, 256, {"map_go": RECORD})
    load = ks.load_values(2**31 + 9)
    bound, op, arg = ks.load_update(7, load)
    assert bound == (7, "map_go", "bench") and op == "update"
    assert [f for f, _a in arg] == list(ks.record("map_go").fields)
    assert all(a[0] == "assign" and len(a[1]) == 100 for _f, a in arg)
    # fixed by the seed and the key
    assert arg == ks.load_update(7, ks.load_values(2**31 + 9))[2]
    assert arg != ks.load_update(7, ks.load_values(1))[2]
    assert arg != ks.load_update(9, load)[2]
    assert arg[3][1][1] == field_value(2**31 + 9, 7, "field3", 100)
    path = tmp_path / "m.json"
    path.write_text(json.dumps(mix_doc(operations={"update_only_txn": 1})))
    s = ClientStream(Mix.from_file(str(path)), ks, 5, 0)
    fields = collections.Counter()
    values = set()
    for _ in range(3000):
        [(key, op, arg)] = s.next().updates
        [((name, ftype), (assign, value))] = arg
        assert op == "update" and assign == "assign"
        assert ftype == "register_lww" and len(value) == 100
        fields[name] += 1
        values.add(value)
    # one field of ten, chosen alike; a fresh value every time
    assert set(fields) == {f"field{i}" for i in range(10)}
    assert min(fields.values()) > 0.85 * 300
    assert len(values) == 3000


@pytest.mark.parametrize("types,says", [
    ({"map_go": {"fields": {"register_lww": 10}}}, "field_bytes"),
    ({"map_go": {"fields": {"counter_pn": 10}, "field_bytes": 100}},
     "field type 'counter_pn'"),
    ({"map_go": {"fields": {"register_lww": 10}, "field_bytes": 100,
                 "order": "hashed"}}, "fields and field_bytes"),
    ({"map_go": {"fields": {}, "field_bytes": 100}}, "no fields"),
    ({"map_go": {"fields": {"register_lww": 0}, "field_bytes": 100}},
     "0 fields"),
    ({"map_go": {"fields": {"register_lww": 10}, "field_bytes": 0}},
     "field_bytes 0"),
    # map_rr resets a removed field; a register_lww has no reset
    ({"map_rr": {"fields": {"register_lww": 10}, "field_bytes": 100}},
     "no reset"),
    ({"counter_pn": 3, "rga": 1}, "'rga' is none of"),
    ({"counter_pn": 0}, "weight 0"),
    ({"counter_pn": 1.5}, "weight 1.5"),
    ({}, "names no type"),
])
def test_a_type_the_harness_cannot_judge_is_refused_with_the_files_path(
        tiny_root, types, says):
    cell = add_cell(tiny_root, "ycsb", types)
    with pytest.raises(ValueError, match="benchmark/configs/ycsb.json") as e:
        harness.load_cell(tiny_root, cell)
    assert says in str(e.value)


@pytest.mark.parametrize("entry", [
    {"kind": "ycsb_zipfian", "theta": 0.9},
    {"kind": "ycsb_zipfian", "item_count": 10**6},
    {"kind": "scrambled_zipfian"},
])
def test_a_malformed_zipfian_entry_is_refused_with_the_files_path(
        tiny_root, entry):
    cell = add_cell(tiny_root, "ycsb", {"map_go": RECORD},
                    key_generator=entry)
    with pytest.raises(ValueError, match="read50-zipfian.json"):
        harness.load_cell(tiny_root, cell)


# ------------------------------------------------- the source's Zipfian

#: the closed form of the source's constants: the first item takes
#: 1 / zeta(10^10, 0.99), the second 0.5^0.99 of that
FIRST = 1.0 / ZIPF_ZETAN
SECOND = 0.5 ** ZIPF_THETA / ZIPF_ZETAN


def test_the_closed_form_of_the_sources_constants():
    assert FIRST == pytest.approx(0.03778, abs=1e-5)
    assert SECOND == pytest.approx(0.01902, abs=1e-5)


def test_fnvhash64_is_fnv1a_over_eight_bytes_low_first():
    def fnv(x):
        h = 0xCBF29CE484222325
        for _ in range(8):
            h = ((h ^ (x & 0xFF)) * 1099511628211) % 2**64
            x >>= 8
        h = h - 2**64 if h >= 2**63 else h
        return abs(h)

    xs = [0, 1, 2, 255, 256, 10**10, 2**40 + 3, 123456789]
    assert fnvhash64(np.array(xs, np.int64)).tolist() == [fnv(x) for x in xs]


@pytest.mark.parametrize("seed", [7, 2**31 + 11, 3_000_000_019])
def test_ycsb_zipfian_is_the_sources_closed_form(seed):
    from benchmark.traffic import KEY_GENERATORS, rng_for

    n_keys = 4 * 131072
    keys = KEY_GENERATORS["ycsb_zipfian"](n_keys)(rng_for(seed, 1, 0),
                                                  200_000)
    assert len(keys) == 200_000
    assert keys.min() >= 0 and keys.max() < n_keys
    counts = np.bincount(keys, minlength=n_keys)
    order = np.argsort(-counts, kind="stable")
    share = counts[order] / len(keys)
    assert abs(share[0] - FIRST) < 0.003
    assert abs(share[1] - SECOND) < 0.0025
    # scrambled: the hottest keys are the hash's of items 0 and 1
    assert order[0] == fnvhash64(np.array([0]))[0] % n_keys
    assert order[1] == fnvhash64(np.array([1]))[0] % n_keys
    # and the 1,000 hottest lie on every partition
    by_partition = np.bincount(order[:1000] % 4, minlength=4)
    assert by_partition.min() > 0.8 * by_partition.mean()


@pytest.mark.parametrize("ops", [{"read_only_txn": 1},
                                 {"update_only_txn": 1}])
def test_a_mixs_reads_and_updates_draw_through_ycsb_zipfian(tmp_path, ops):
    ks = Keyspace.of(4, 131072, {"map_go": RECORD})
    path = tmp_path / "m.json"
    path.write_text(json.dumps(mix_doc(operations=ops)))
    s = ClientStream(Mix.from_file(str(path)), ks, 2**31 + 3, 1)
    keys = []
    for _ in range(20_000):
        txn = s.next()
        keys += txn.read_keys or [k for k, _op, _arg in txn.updates]
    counts = collections.Counter(keys)
    [(hottest, n)] = counts.most_common(1)
    assert len(keys) == 20_000
    assert hottest == fnvhash64(np.array([0]))[0] % ks.n_keys
    assert abs(n / len(keys) - FIRST) < 0.006


# --------------------------------------------------- the reference


def record_history():
    ks = Keyspace.of(2, 16, {"counter_pn": 1, "map_go": RECORD})
    load = ks.load_values(3)
    return ks, reference.PlainHistory(ks, load), load


def assign(field: str, value: bytes) -> list:
    return [((field, "register_lww"), ("assign", value))]


def test_a_record_reads_as_its_fields_last_writes():
    ks, h, load = record_history()
    key = 2  # row 1: a record
    assert ks.type_of(key) == "map_go"
    loaded = h.at(key)
    assert loaded == {(f"field{i}", "register_lww"):
                      field_value(3, key, f"field{i}", 100)
                      for i in range(10)}
    h.write(key, 100, "update", assign("field4", b"a" * 100))
    h.write(key, 200, "update", assign("field4", b"b" * 100))
    h.write(key, 300, "update", assign("field7", b"c" * 100))
    assert h.at(key, 99) == loaded
    assert h.at(key, 150) == {
        **loaded, ("field4", "register_lww"): b"a" * 100}
    assert h.at(key, 300) == h.at(key) == {
        **loaded, ("field4", "register_lww"): b"b" * 100,
        ("field7", "register_lww"): b"c" * 100}
    # the counter beside it is untouched
    assert h.at(0) == int(load.incs[0])


def test_a_multi_value_register_reads_as_a_list_of_its_value():
    ks = Keyspace.of(2, 16, TYPES["ycsb-rr"])
    h = reference.PlainHistory(ks, ks.load_values(3))
    h.write(5, 10, "update",
            [(("field2", "register_mv"), ("assign", b"x" * 100))])
    got = h.at(5)
    assert got[("field2", "register_mv")] == [b"x" * 100]
    assert got[("field0", "register_mv")] == [field_value(3, 5, "field0",
                                                          100)]


def test_the_stale_control_one_field_one_write_late_is_wrong():
    ks, h, _load = record_history()
    key = 2
    recs = []
    for i, (t, field) in enumerate([(100, "field1"), (200, "field1"),
                                    (300, "field8")]):
        recs.append({"client": 0, "kind": "update_only_txn", "ok": True,
                     "read_keys": [], "values": None,
                     "updates": [(key, "update",
                                  assign(field, bytes([65 + i]) * 100))],
                     "snapshot_time": None, "commit_time": t,
                     "clock_sent": None, "aborts": 0})
    reference.feed(h, recs)
    reads = [{"client": 1, "kind": "read_only_txn", "ok": True,
              "read_keys": [key], "updates": [], "snapshot_time": s,
              "values": [h.at(key, s)], "commit_time": None,
              "clock_sent": None, "aborts": 0} for s in (250, 350)]
    assert reference.wrong_reads(h, reads)[:2] == (2, 0)
    stale = reference.StaleHistory(ks, h.load)
    stale._hist = h._hist
    late = stale.at(key, 350)
    differs = [f for f in late if late[f] != h.at(key, 350)[f]]
    assert differs == [("field8", "register_lww")]
    control = reference.control_numbers(h, recs + reads, {"keys": [key]})
    assert control["reads_wrong"] == 2 and control["acks_unreadable"] == 1


def test_under_certification_commit_order_is_the_registers_order(
        tmp_path):
    """The premise the reference stands on, against the program: of two
    writers of one record's field that overlap, one aborts, so the
    register's own last-writer order (its timestamps) and commit order
    agree: every read, at the snapshot it returns, holds the value of
    the last write committed at or before it."""
    from antidote_tpu.api import AntidoteTPU
    from antidote_tpu.clocks import VC
    from antidote_tpu.config import Config

    ks = Keyspace.of(1, 4, {"map_go": RECORD})
    db = AntidoteTPU(dc_id="dc1", config=Config(
        n_partitions=1, flight_recorder_dir=str(tmp_path / "obs")),
        data_dir=str(tmp_path / "data"))
    load = ks.load_values(4)
    db.update_objects_static(None, [ks.load_update(0, load)])
    commits, reads = [], []
    lock = threading.Lock()
    done = threading.Event()

    def writer(w: int) -> None:
        clock = None
        for i in range(40):
            value = b"%d-%d" % (w, i) + bytes(95)
            try:
                clock = db.update_objects_static(clock, [
                    (ks.bound(0), "update", assign("field0", value))])
            except Exception as e:  # an abort of certification
                assert "snapshot" in str(e) or "concurrent" in str(e), e
                continue
            with lock:
                commits.append((clock.get_dc("dc1"), value))

    def reader(clock=None) -> None:
        while True:
            [got], snap = db.read_objects_static(clock, [ks.bound(0)])
            with lock:
                reads.append((snap.get_dc("dc1"), got))
            if clock is not None or done.is_set():
                return

    try:
        writers = [threading.Thread(target=writer, args=(w,))
                   for w in range(4)]
        readers = [threading.Thread(target=reader) for _ in range(2)]
        for t in writers + readers:
            t.start()
        for t in writers:
            t.join()
        done.set()
        for t in readers:
            t.join()
        # one more, at the newest commit's clock
        reader(VC({"dc1": max(c for c, _v in commits)}))
    finally:
        db.close()
    h = reference.PlainHistory(ks, load)
    for commit, value in sorted(commits):
        h.write(0, commit, "update", assign("field0", value))
    assert len(commits) > 40 and len(reads) > 20
    assert len({c for c, _v in commits}) == len(commits)
    wrong = [(snap, got) for snap, got in reads if got != h.at(0, snap)]
    assert not wrong, wrong[:2]
    assert reads[-1][1] == h.at(0) != h.at(0, commits[0][0] - 1)


# ------------------------------------------------ the bytes a record needs


def test_needed_bytes_of_a_record_from_the_planes_own_shapes():
    import tempfile

    from antidote_tpu.api import AntidoteTPU
    from antidote_tpu.config import Config

    db = AntidoteTPU(dc_id="dc1", config=Config(n_partitions=2),
                     data_dir=tempfile.mkdtemp(prefix="bench_rows_"))
    try:
        rows = trace.plane_row_bytes(db)
    finally:
        db.close()
    lww, go = rows["register_lww"], rows["set_go"]
    assert lww["key_row"] > lww["op_row"] > 0 and go["key_row"] > 0
    ks = Keyspace.of(2, 64, {"counter_pn": 1, "map_go": RECORD,
                             "map_rr": TYPES["ycsb-rr"]["map_rr"]})
    read = {"ok": True, "read_keys": [2], "updates": []}       # map_go
    write = {"ok": True, "read_keys": [],
             "updates": [(2, "update", assign("field3", b"x"))]}
    keys_read, ops = trace.rows_of_work(ks, [read, write], dcs=2)
    assert keys_read == {"register_lww": 10, "set_go": 1}
    assert ops == {"register_lww": 2}
    assert trace.needed_bytes(rows, keys_read, {}) == \
        10 * lww["key_row"] + go["key_row"]
    assert trace.needed_bytes(rows, {}, trace.rows_of_work(
        ks, [write])[1]) == lww["op_row"]
    # a map_rr keeps no presence plane; a flat key is its own row
    keys_read, _ = trace.rows_of_work(ks, [{"read_keys": [4, 0],
                                            "updates": []}])
    assert keys_read == {"register_mv": 10, "counter_pn": 1}


# --------------------------------------------- a whole run, as data only


@pytest.mark.parametrize("config", sorted(TYPES))
def test_a_record_cell_added_as_data_runs_whole_with_no_value_wrong(
        tiny_root, on_the_cpu, capsys, config):  # noqa: F811
    before = {}
    for d, _dirs, files in os.walk(tiny_root):
        for name in files:
            with open(os.path.join(d, name), "rb") as f:
                before[os.path.join(d, name)] = f.read()
    cell = add_cell(tiny_root, config, TYPES[config])
    rc = run.main(["--workload", cell, "--seed", str(2**31 + 42),
                   "--seconds", "3", "--trace", "0"], root=tiny_root)
    line, _out = last_line(capsys)
    compared = {k: v["value"] for k, v in line["compared"].items()}
    assert rc == 0 and line["failed"] == 0, compared
    for name in ("reads_wrong", "acks_unreadable",
                 "snapshots_behind_session", "error_logs"):
        assert compared[name] == 0, (name, compared)
    assert compared["reads_compared"] > 50
    assert compared["acks_read_back"] > 50
    # ``correct`` also asks that reads reach the device: a map_rr of
    # multi-value registers does; a register_lww write is kept on the
    # host path by the program (PERF.md, section 7), and the run says so
    reached = compared["device_read_dispatches"] > 0
    assert line["correct"] is reached, compared
    assert reached or config == "ycsb"
    assert {"txn_per_s", "setup_s"} <= set(line["metrics"])
    # what the tree had is as it was: only files and entries were added
    for path, content in before.items():
        if not path.endswith("BENCHMARK.json"):
            with open(path, "rb") as f:
                assert f.read() == content, path


@pytest.mark.parametrize("config", sorted(TYPES))
def test_check_seeds_takes_a_record_configuration(tiny_root, on_the_cpu,
                                                  capsys,  # noqa: F811
                                                  config):
    cell = add_cell(tiny_root, config, TYPES[config])
    rc = check_seeds.main(["--workload", cell, "--seeds", "5,6",
                           "--seconds", "2"], root=tiny_root)
    seeds = [json.loads(line[5:]) for line in
             capsys.readouterr().out.splitlines()
             if line.startswith("SEED ")]
    assert [s["seed"] for s in seeds] == [5, 6]
    assert rc == (0 if all(s["correct"] for s in seeds) else 1)
    for s in seeds:
        assert s["program"]["reads_wrong"] == 0
        assert s["program"]["acks_unreadable"] == 0
        # the control answers one field one write late: wrong
        assert s["control"]["reads_wrong"] > 0
        assert s["control"]["acks_unreadable"] > 0


def altered_field(monkeypatch):
    """Every twentieth read's first record comes back with one field
    changed, where the answer is produced."""
    from antidote_tpu.api import AntidoteTPU

    real = AntidoteTPU.read_objects_static
    calls = [0]

    def altered(self, clock, objects, properties=None):
        values, vc = real(self, clock, objects, properties)
        calls[0] += 1
        if calls[0] % 20 == 0 and isinstance(values[0], dict):
            first = dict(values[0])
            f = min(first)
            first[f] = [b"?"] if isinstance(first[f], list) else b"?"
            values = [first] + list(values[1:])
        return values, vc

    monkeypatch.setattr(AntidoteTPU, "read_objects_static", altered)
    return "reads_wrong"


def unchanged_state(monkeypatch):
    """Every update acknowledged with the records left as they were."""
    from antidote_tpu.pb import antidote_pb2 as pb
    from antidote_tpu.pb.server import _Connection

    real = _Connection._HANDLERS[pb.ApbStaticUpdateObjects]

    def unchanged(self, req):
        del req.updates[:]
        return real(self, req)

    monkeypatch.setitem(_Connection._HANDLERS, pb.ApbStaticUpdateObjects,
                        unchanged)
    return "acks_unreadable"


@pytest.mark.parametrize("fault", [altered_field, unchanged_state])
def test_a_record_cell_whose_timed_path_is_broken_is_not_correct(
        tiny_root, on_the_cpu, capsys, monkeypatch, fault):  # noqa: F811
    cell = add_cell(tiny_root, "ycsb-rr", TYPES["ycsb-rr"])
    caught_by = fault(monkeypatch)
    rc = run.main(["--workload", cell, "--seed", "77", "--seconds", "2",
                   "--trace", "0"], root=tiny_root)
    line, _out = last_line(capsys)
    compared = {k: v["value"] for k, v in line["compared"].items()}
    assert rc == 0 and line["correct"] is False
    assert compared[caught_by] > 0, compared
