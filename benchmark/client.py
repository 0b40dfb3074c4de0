"""One closed-loop client, a process of its own: a writer sends the
mix's transactions to the origin DC; a prober commits one update there
and reads the same keys back at a second DC at the returned clock.

Started by harness.py with its parameters as one JSON argument; obeys
lines on stdin (``run <phase> <t_start> <t_end>`` on the machine's
monotonic clock, which all processes of one host share) and answers
each with one line on stdout once the phase's records are written.

It never opens the chip: the chip belongs to the one process that
serves, and a second process that touched it would fail or hang.  The
package import pulls JAX in, so the platform is pinned to the CPU here,
in the child only.
"""

from __future__ import annotations

import json
import os
import pickle
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from antidote_tpu.pb.client import (  # noqa: E402
    PbClient,
    PbError,
    PbServerError,
)
from benchmark.traffic import (  # noqa: E402
    ORIGIN_DC,
    ClientStream,
    Keyspace,
    Mix,
    Txn,
)


#: how the server words a write-write certification abort
#: (txn/manager.py ``CertificationError``); any other error reply, and
#: a transaction still aborted ``Mix.retry_for_s`` after its first
#: send, counts in ``failed``
ABORT_MARKS = ("committed after snapshot", "prepared by concurrent txn")


class Client:
    def __init__(self, p: dict):
        self.p = p
        self.ks = Keyspace.of(p["n_partitions"], p["keys_per_partition"],
                              p["types"])
        self.mix = Mix.from_file(p["mix_file"])
        self.stream = ClientStream(self.mix, self.ks, p["seed"],
                                   p["client"])
        self.cl = PbClient(port=p["port"], timeout=p["timeout_s"])
        #: a prober's second server: the DC it reads its own writes at
        self.remote = (PbClient(port=p["remote_port"],
                                timeout=p["timeout_s"])
                       if p.get("remote_port") else None)
        self.clock = None  # the session's causal clock

    def close(self) -> None:
        self.cl.close()
        if self.remote is not None:
            self.remote.close()

    def one(self, txn, cl=None, dc: str = ORIGIN_DC) -> dict:
        """Send one transaction to ``cl`` (the origin DC's server by
        default) and wait for its answer; one that certification aborts
        is sent again after a pause, and its latency runs from the
        first send to the last answer."""
        ks, cl = self.ks, cl or self.cl
        rec = {"client": self.p["client"], "dc": dc, "kind": txn.kind,
               "ok": False,
               "read_keys": txn.read_keys, "updates": txn.updates,
               "values": None, "snapshot_time": None, "commit_time": None,
               "clock_sent": (self.clock.get_dc(ORIGIN_DC)
                              if self.clock is not None else None),
               "aborts": 0, "error": None, "t_send": time.monotonic()}
        while True:
            try:
                if txn.kind == "read_only_txn":
                    rec["values"], snap = cl.read_objects_static(
                        self.clock, [ks.bound(k) for k in txn.read_keys])
                    rec["snapshot_time"] = snap.get_dc(ORIGIN_DC)
                    self.clock = snap
                else:
                    commit = cl.update_objects_static(
                        self.clock, [(ks.bound(k), op, arg)
                                     for k, op, arg in txn.updates])
                    rec["commit_time"] = commit.get_dc(ORIGIN_DC)
                    self.clock = commit
                rec["ok"] = True
            except PbError as e:
                # a transport fault leaves the connection unusable
                rec["error"] = f"{type(e).__name__}: {e}"[:300]
                if (isinstance(e, PbServerError)
                        and any(m in str(e) for m in ABORT_MARKS)
                        and time.monotonic() - rec["t_send"]
                        < self.mix.retry_for_s):
                    rec["aborts"] += 1
                    rec["error"] = None
                    time.sleep(self.mix.retry_pause_ms / 1000.0)
                    continue
            break
        rec["t_done"] = time.monotonic()
        return rec

    def probe(self) -> list:
        """A prober's loop: one update at the origin DC and, once it is
        acknowledged, one read of the same keys at the other DC at the
        commit clock it returned; the read's ``t_acked`` is the
        update's acknowledgement.  The session stays the origin's: the
        next update carries that commit clock, not the other DC's
        answer, whose entry for that DC only its heartbeat would
        bring to the origin (a wait of up to a heartbeat period)."""
        update = self.one(self.stream.next("update_only_txn"))
        if not update["ok"]:
            return [update]
        session = self.clock
        keys = [k for k, _op, _arg in update["updates"]]
        read = self.one(Txn("read_only_txn", keys, []), self.remote,
                        self.p["remote_dc"])
        read["t_acked"] = update["t_done"]
        self.clock = session
        return [update, read]

    def run(self, phase: str, t_start: float, t_end: float) -> list:
        # a warm-up's read phase sends the mix's reads only: the read
        # path is where the program keeps a program per pattern; a
        # prober runs its loop in every phase
        kind = "read_only_txn" if phase == "warmreads" else None
        while time.monotonic() < t_start:
            time.sleep(min(0.0005, max(t_start - time.monotonic(), 0)))
        records = []
        while time.monotonic() < t_end:
            recs = (self.probe() if self.remote is not None
                    else [self.one(self.stream.next(kind))])
            records += recs
            if any(r["error"] and "PbServerError" not in r["error"]
                   for r in recs):
                break  # the stream cannot be trusted any more
        return records


def main() -> int:
    p = json.loads(sys.argv[1])
    client = Client(p)
    print("ready", flush=True)
    try:
        for line in sys.stdin:
            word, phase, t_start, t_end = line.split()
            if word != "run":
                raise ValueError(f"unknown command {line!r}")
            records = client.run(phase, float(t_start), float(t_end))
            path = f"{p['out']}.{phase}"
            with open(path, "wb") as f:
                pickle.dump(records, f)
            print(f"done {phase} {len(records)}", flush=True)
    finally:
        client.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
