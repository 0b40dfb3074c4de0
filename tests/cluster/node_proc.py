"""Subprocess node harness for the multi-process-DC tests.

Runs one NodeServer and obeys a line-oriented stdio protocol so the
pytest parent can drive a DC whose partitions live in several OS
processes — the analogue of the reference's ct_slave BEAM peers
(reference test/utils/test_utils.erl:110-165).

Commands (JSON per line on stdin; one JSON reply per line on stdout):
  {"cmd": "addr"}
  {"cmd": "join", "dc": d, "ring": {"0": nid, ...},
   "members": {nid: [host, port], ...}}
  {"cmd": "update", "key": k, "type": t, "op": o, "arg": a,
   "clock": vc|null}
  {"cmd": "read", "key": k, "type": t, "clock": vc|null}
  {"cmd": "stable"}
  {"cmd": "kill"}     — hard-exit without cleanup (crash injection)
  {"cmd": "exit"}     — graceful close
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax

# pinned to the CPU by design: a chip belongs to one process, so a
# multi-process DC needs one chip per member — this is a logic check
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

from antidote_tpu.clocks import VC  # noqa: E402
from antidote_tpu.cluster import NodeServer  # noqa: E402
from antidote_tpu.config import Config  # noqa: E402


def main():
    node_id = sys.argv[1]
    data_dir = sys.argv[2]
    port = int(sys.argv[3])
    faults = sys.argv[4] if len(sys.argv) > 4 else ""
    if "die_in_resize_swap" in faults:
        # crash injection: kill -9 semantics at the nastiest resize
        # point — journal + new plan persisted, staged logs complete,
        # live logs NOT yet swapped (restart must resume via journal)
        from antidote_tpu.txn.node import Node

        def dying(self, old_n, new_n):
            os._exit(9)

        Node._complete_resize_swap = dying
    srv = NodeServer(node_id, port=port, data_dir=data_dir,
                     config=Config(heartbeat_s=0.02, sync_log=True,
                                   clock_wait_timeout_s=20.0))

    def out(obj):
        sys.stdout.write(json.dumps(obj) + "\n")
        sys.stdout.flush()

    out({"ready": True, "addr": list(srv.addr),
         "assembled": srv.node is not None})
    for line in sys.stdin:
        try:
            req = json.loads(line)
            cmd = req["cmd"]
            if cmd == "addr":
                out({"addr": list(srv.addr)})
            elif cmd == "join":
                srv.install_cluster(
                    req["dc"],
                    {int(p): nid for p, nid in req["ring"].items()},
                    {nid: tuple(a) for nid, a in req["members"].items()})
                out({"ok": True})
            elif cmd == "update":
                clock = VC(req["clock"]) if req.get("clock") else None
                ct = srv.api.update_objects_static(
                    clock,
                    [((req["key"], req["type"], "b"), req["op"],
                      req["arg"])])
                out({"clock": dict(ct)})
            elif cmd == "read":
                clock = VC(req["clock"]) if req.get("clock") else None
                vals, cvc = srv.api.read_objects_static(
                    clock, [(req["key"], req["type"], "b")])
                out({"value": vals[0], "clock": dict(cvc)})
            elif cmd == "stable":
                out({"stable": dict(
                    srv.plane.get_stable_snapshot())})
            elif cmd == "resize":
                ring = srv.resize_cluster(int(req["n"]))
                out({"ring": {str(p): o for p, o in ring.items()}})
            elif cmd == "width":
                out({"n": srv.node.config.n_partitions,
                     "parked": srv._resize_parking})
            elif cmd == "kill":
                os._exit(9)
            elif cmd == "exit":
                srv.close()
                out({"ok": True})
                return
            else:
                out({"error": f"unknown cmd {cmd!r}"})
        except Exception as e:  # noqa: BLE001 — report, keep serving
            out({"error": f"{type(e).__name__}: {e}"})


if __name__ == "__main__":
    main()
