"""The plain reference and the comparison that decides ``correct``.

Python ints, sets and dicts of field values, one history per written
key, fed the operations that all the clients report as acknowledged, in
the order of their commit times: any client may write any key, and
under write-write certification two transactions that wrote one key
never overlap, so the commit times order each key's writes (and a
register's own last-writer order agrees with them).  It imports nothing
of the program.  What the configuration's guarantees say a read must
return is then exact: a Clock-SI snapshot read at local time ``s`` sees every
write that committed at or before ``s`` and none after, and a session's
transaction is answered at or after the clock the session sent.

``judge`` counts, over every read the clients made in the window, the
values that differ and the snapshots that lie behind the session's
clock; over the acknowledged writes read back once the window has
closed, the keys that differ; and the transactions that failed (an
error reply, a timeout, one still aborted after its retries).  Each
count is compared with its limit and printed beside it.
"""

from __future__ import annotations

import bisect

from benchmark.traffic import ELEMS, ORIGIN_DC, field_value


class PlainHistory:
    """Every key's value over commit time, from the load's values
    (``traffic.Load``) on."""

    def __init__(self, keyspace, load):
        self.ks = keyspace
        self.load = load
        #: key -> ([commit times], [state after that commit])
        self._hist: dict = {}

    def _loaded(self, key: int):
        t = self.ks.type_of(key)
        if t == "counter_pn":
            return int(self.load.incs[key])
        if t == "set_aw":
            m = int(self.load.masks[key])
            return frozenset(e for i, e in enumerate(ELEMS) if m >> i & 1)
        rec = self.ks.record(t)
        return {f: field_value(self.load.seed, key, f[0], rec.field_bytes)
                for f in rec.fields}

    def write(self, key: int, commit_time: int, op: str, arg) -> None:
        times, states = self._hist.setdefault(key, ([], []))
        state = states[-1] if states else self._loaded(key)
        if op == "increment":
            state = state + arg
        elif op == "decrement":
            state = state - arg
        elif op == "add":
            state = state | {arg}
        elif op == "remove":
            state = state - {arg}
        elif op == "update":
            # a record's fields, each assigned whole: a register's value
            # is its last write committed, since two writers of one key
            # that overlap cannot both commit
            state = dict(state)
            for f, (nested, value) in arg:
                if nested != "assign":
                    raise ValueError(f"unknown field operation {nested!r}")
                state[f] = value
        else:
            raise ValueError(f"unknown operation {op!r}")
        if times and times[-1] == commit_time:
            states[-1] = state  # a second write of one transaction
            return
        if times and times[-1] > commit_time:
            raise ValueError(
                f"key {key}: fed out of commit order "
                f"({times[-1]} then {commit_time})")
        times.append(commit_time)
        states.append(state)

    def at(self, key: int, snapshot_time: int | None = None):
        """The value a read of ``key`` must return at a snapshot (the
        newest value where none is given), in the form the wire gives
        it: an int; a sorted list of elements; for a record, a dict from
        each ``(field, field type)`` to its value, a ``register_mv``'s
        as a list of its one value."""
        hist = self._hist.get(key)
        if hist is None:
            state = self._loaded(key)
        else:
            times, states = hist
            i = len(times) if snapshot_time is None \
                else bisect.bisect_right(times, snapshot_time)
            state = states[i - 1] if i else self._loaded(key)
        if isinstance(state, dict):
            return {f: [v] if f[1] == "register_mv" else v
                    for f, v in state.items()}
        return state if isinstance(state, int) else sorted(state)


class StaleHistory(PlainHistory):
    """The control: the reference put in the program's place with one
    stated guarantee broken — an acknowledged write is not yet readable
    at its commit clock; it shows one write of that key late (of a
    record, the one field that write assigned)."""

    def at(self, key: int, snapshot_time: int | None = None):
        hist = self._hist.get(key)
        if hist is None:
            return super().at(key, snapshot_time)
        times, _states = hist
        i = len(times) if snapshot_time is None \
            else bisect.bisect_right(times, snapshot_time)
        before = times[i - 2] if i >= 2 else -1
        return super().at(key, before)


def by_dc(records: list) -> tuple:
    """(the records answered at the origin DC, those answered at any
    other: the probers' reads).  A record without ``dc`` is the
    origin's."""
    local = [r for r in records if r.get("dc", ORIGIN_DC) == ORIGIN_DC]
    return local, [r for r in records
                   if r.get("dc", ORIGIN_DC) != ORIGIN_DC]


def feed(history: PlainHistory, records: list) -> None:
    """Apply every acknowledged update of all the clients' records, in
    the order of the commit times."""
    acked = [r for r in records if r["ok"] and r["updates"]]
    for rec in sorted(acked, key=lambda r: r["commit_time"]):
        for key, op, arg in rec["updates"]:
            history.write(key, rec["commit_time"], op, arg)


def wrong_reads(history: PlainHistory, records: list,
                answers=None) -> tuple:
    """(values compared, values that differ, first few differences)
    over the reads of ``records``.  ``answers(rec)`` stands in for the
    values the program returned (the control passes its own)."""
    compared = wrong = 0
    first: list = []
    for rec in records:
        if not rec["ok"] or not rec["read_keys"]:
            continue
        got = rec["values"] if answers is None else answers(rec)
        snap = rec["snapshot_time"]
        for key, value in zip(rec["read_keys"], got):
            compared += 1
            want = history.at(key, snap)
            if value != want:
                wrong += 1
                if len(first) < 3:
                    first.append(f"client {rec['client']} {rec['kind']} "
                                 f"key {key} at {snap}: read {value!r}, "
                                 f"the reference holds {want!r}")
    return compared, wrong, first


def behind_session(records: list) -> tuple:
    """(transactions that carried a session clock, those answered
    behind it): a read's snapshot, or an update's commit time, earlier
    than the clock the session sent with it."""
    carried = behind = 0
    for rec in records:
        if not rec["ok"] or rec["clock_sent"] is None:
            continue
        carried += 1
        at = rec["snapshot_time"] if rec["read_keys"] \
            else rec["commit_time"]
        behind += at is None or at < rec["clock_sent"]
    return carried, behind


def control_numbers(history: PlainHistory, records: list,
                    readback: dict) -> dict:
    """What the comparison reads when the control answers in the
    program's place.  Visibility broken: every read of the window and
    every key of the read-back answered by ``StaleHistory`` over the
    same histories.  Causal sessions broken: every transaction answered
    at the clock the session sent with the transaction before it, one
    answer late.  Where probers read at another DC (``dc`` on a record
    other than the origin), replication broken: each such read answered
    at the commit clock it was sent with, less one: the write it should
    see not yet applied there (``remote_reads_wrong``).  The other
    remote numbers are held by faults driven under the harness
    (tests/benchmark/test_bb2dc.py), not by this control: a snapshot
    below its commit clock is what the control answers by construction,
    and the read-back at another DC asks the keys the origin's does."""
    local, remote = by_dc(records)
    stale = StaleHistory(history.ks, history.load)
    stale._hist = history._hist
    _c, wrong, _f = wrong_reads(
        history, local,
        answers=lambda rec: [stale.at(k, rec["snapshot_time"])
                             for k in rec["read_keys"]])
    unreadable = sum(stale.at(k) != history.at(k)
                     for k in readback["keys"])
    behind, last = 0, {}
    for rec in local:  # each client's records are in its own order
        before = last.get(rec["client"])
        last[rec["client"]] = sent = rec["clock_sent"]
        behind += (rec["ok"] and sent is not None
                   and before is not None and before < sent)
    out = {"reads_wrong": wrong, "acks_unreadable": unreadable,
           "snapshots_behind_session": behind}
    if "remote" in readback:
        _c, out["remote_reads_wrong"], _f = wrong_reads(
            history, remote,
            answers=lambda rec: [history.at(k, rec["clock_sent"] - 1)
                                 for k in rec["read_keys"]])
    return out


def judge(numbers: list) -> bool:
    """``numbers``: (name, value, comparison, limit) with comparison
    ``<=`` or ``>=``.  True when every number keeps its limit."""
    return all(value <= limit if cmp_ == "<=" else value >= limit
               for _name, value, cmp_, limit in numbers)


def compared_lines(numbers: list) -> tuple:
    """The numbers compared, each beside its limit: as lines for
    standard error and as the object the result line carries last."""
    lines = [f"compared {name} = {value} (limit {cmp_} {limit})"
             for name, value, cmp_, limit in numbers]
    obj = {name: {"value": value, "limit": limit, "cmp": cmp_}
           for name, value, cmp_, limit in numbers}
    return lines, obj
