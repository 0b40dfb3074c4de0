"""Subprocess DC harness for cross-process transport tests.

Runs one DataCenter over the TCP transport and obeys a line-oriented
stdio protocol so the pytest parent can drive a multi-process cluster —
the analogue of the reference's ct_slave BEAM peers with real sockets
(reference test/utils/test_utils.erl:110-165).

Commands (JSON per line on stdin; one JSON reply per line on stdout):
  {"cmd": "descriptor"}
  {"cmd": "connect", "desc": [dc_id, n_partitions, [[host, pub]], [[host, q]]]}
  {"cmd": "update", "key": k, "type": t, "op": o, "arg": a, "clock": vc|null}
  {"cmd": "read", "key": k, "type": t, "clock": vc|null}
  {"cmd": "fabric"}   — which publish plane is live (native hub?)
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
from antidote_tpu.config import Config  # noqa: E402
from antidote_tpu.interdc.dc import DataCenter  # noqa: E402
from antidote_tpu.interdc.tcp import transport_from_config  # noqa: E402
from antidote_tpu.interdc.wire import DcDescriptor  # noqa: E402


def main():
    dc_id = sys.argv[1]
    data_dir = sys.argv[2]
    pub_port = int(sys.argv[3])
    query_port = int(sys.argv[4])
    cfg = Config(n_partitions=2, heartbeat_s=0.02,
                 clock_wait_timeout_s=20.0, sync_log=True)
    bus = transport_from_config(cfg, pub_port=pub_port,
                                query_port=query_port)
    dc = DataCenter(dc_id, bus, config=cfg, data_dir=data_dir)
    dc.start_bg_processes()

    def out(obj):
        sys.stdout.write(json.dumps(obj) + "\n")
        sys.stdout.flush()

    out({"ready": True})
    for line in sys.stdin:
        try:
            req = json.loads(line)
            cmd = req["cmd"]
            if cmd == "descriptor":
                d = dc.descriptor()
                out({"desc": [d.dc_id, d.n_partitions,
                              [list(a) for a in d.pub_addrs],
                              [list(a) for a in d.logreader_addrs]]})
            elif cmd == "connect":
                did, np_, pub, q = req["desc"]
                dc.observe_dc(DcDescriptor(
                    dc_id=did, n_partitions=np_,
                    pub_addrs=tuple(tuple(a) for a in pub),
                    logreader_addrs=tuple(tuple(a) for a in q)))
                out({"ok": True})
            elif cmd == "update":
                clock = VC(req["clock"]) if req.get("clock") else None
                ct = dc.update_objects_static(
                    clock,
                    [((req["key"], req["type"], "b"), req["op"],
                      req["arg"])])
                out({"clock": dict(ct)})
            elif cmd == "read":
                clock = VC(req["clock"]) if req.get("clock") else None
                vals, cvc = dc.read_objects_static(
                    clock, [(req["key"], req["type"], "b")])
                out({"value": vals[0], "clock": dict(cvc)})
            elif cmd == "fabric":
                out({"hub": bus._hub is not None,
                     "staged": bus._staged})
            elif cmd == "kill":
                os._exit(1)
            elif cmd == "exit":
                dc.close()
                out({"ok": True})
                return
            else:
                out({"error": f"unknown cmd {cmd}"})
        except Exception as e:  # noqa: BLE001 — report, keep serving
            out({"error": f"{type(e).__name__}: {e}"})


if __name__ == "__main__":
    main()
