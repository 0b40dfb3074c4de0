"""Subprocess worker for config6's cluster mode: one NodeServer plus a
self-driving client loop, stdio-controlled by the parent bench.

The GIL caps any ONE Python process's control plane; the framework's
scale-out axis is the multi-process DC (antidote_tpu/cluster/).  Each
worker drives the update-heavy mix against its own node — mostly its
own ring slice, with a cross-node fraction so the fabric RPC stays in
the measured path (like riak smart clients routing by key while some
requests still hop).

Protocol (JSON lines):
  {"cmd": "join", "dc": d, "ring": {...}, "members": {...}}
  {"cmd": "run", "txns": N, "keys": K, "cross": 0.1, "seed": s}
      -> {"txns": n, "secs": t, "aborts": a}
  {"cmd": "exit"}
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax

# pinned to the CPU by design: a chip belongs to one process, so a
# multi-process DC needs one chip per member — this is a logic check
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

# a node process serves fabric RPCs from threads while its own workload
# runs: the default 5 ms GIL switch interval turns every cross-node
# round trip into a multi-ms scheduling stall
sys.setswitchinterval(0.0001)

import numpy as np  # noqa: E402

from antidote_tpu.cluster import NodeServer  # noqa: E402
from antidote_tpu.config import Config  # noqa: E402
from antidote_tpu.txn.coordinator import TransactionAborted  # noqa: E402


def run_mix(api, seed, txns, own_keys, other_keys, cross, threads=4):
    """The config6 update-heavy mix (80% 1r+2w, 20% 3r) over this
    node's key slice, with a ``cross`` fraction of remote-owned keys —
    the same fresh-transaction pattern as run_direct (comparable
    numbers; smart clients route by owner, like riak's).

    Driven by several concurrent client threads per node (the
    basho_bench shape, reference README "Benchmarking"): a cross-node
    transaction's fabric wait releases the GIL, so concurrent clients
    keep LOCAL transactions flowing underneath it — with one client
    per node, every remote round trip would stall the whole node."""
    import threading

    own = np.asarray(own_keys if own_keys else other_keys,
                     dtype=np.int64)
    other = np.asarray(other_keys if other_keys else own_keys,
                       dtype=np.int64)
    counts = [[0, 0] for _ in range(threads)]
    errs = []

    def worker(t):
        # remainder spread over the first threads: exactly `txns` run
        per = txns // threads + (1 if t < txns % threads else 0)
        rng = np.random.default_rng(seed * 1000 + t)

        def pick():
            if rng.random() < cross:
                return int(other[int(rng.integers(len(other)))])
            return int(own[int(rng.integers(len(own)))])

        try:
            for _ in range(per):
                try:
                    if rng.random() < 0.8:
                        tx = api.start_transaction()
                        api.read_objects(
                            [(pick(), "counter_pn", "b")], tx)
                        # set keys offset by a multiple of the
                        # partition count: disjoint from the counter
                        # keyspace (one key = one type), same ring
                        # owner (affinity preserved)
                        api.update_objects(
                            [((pick(), "counter_pn", "b"),
                              "increment", 1),
                             ((pick() + (1 << 20), "set_aw", "b"),
                              "add", "x")],
                            tx)
                        api.commit_transaction(tx)
                    else:
                        tx = api.start_transaction()
                        api.read_objects(
                            [(pick(), "counter_pn", "b")
                             for _ in range(3)], tx)
                        api.commit_transaction(tx)
                    counts[t][0] += 1
                except TransactionAborted:
                    counts[t][1] += 1
        except BaseException as e:  # noqa: BLE001 — re-raised below
            errs.append(e)

    ths = [threading.Thread(target=worker, args=(t,))
           for t in range(threads)]
    t0 = time.perf_counter()
    for th in ths:
        th.start()
    for th in ths:
        th.join()
    dt = time.perf_counter() - t0
    if errs:
        raise errs[0]
    done = sum(c[0] for c in counts)
    aborts = sum(c[1] for c in counts)
    return done, aborts, dt


def main():
    node_id = sys.argv[1]
    data_dir = sys.argv[2]
    port = int(sys.argv[3])
    # gossip at 0.2 s: each tick costs a peer RPC (~ms under GIL load),
    # and the workload's fresh transactions only need the stable plane
    # for causal floors, not throughput
    srv = NodeServer(node_id, port=port, data_dir=data_dir,
                     config=Config(n_partitions=8, sync_log=False,
                                   heartbeat_s=0.2,
                                   cluster_gossip_s=0.2))

    def out(obj):
        sys.stdout.write(json.dumps(obj) + "\n")
        sys.stdout.flush()

    out({"ready": True, "addr": list(srv.addr),
         "fabric": srv.fabric_kind()})
    for line in sys.stdin:
        req = json.loads(line)
        cmd = req["cmd"]
        try:
            if cmd == "join":
                srv.install_cluster(
                    req["dc"],
                    {int(p): nid for p, nid in req["ring"].items()},
                    {nid: tuple(a)
                     for nid, a in req["members"].items()},
                    fabric=req.get("fabric"),
                    clients=req.get("clients"))
                out({"ok": True})
            elif cmd == "run":
                prof = None
                if os.environ.get("CLUSTER_NODE_PROFILE"):
                    import cProfile

                    prof = cProfile.Profile()
                    prof.enable()
                K = req["keys"]
                # key ownership derives from the node's own ring
                ring = srv.node.ring
                npart = len(ring)
                own = [x for x in range(K)
                       if ring[x % npart] == srv.node_id]
                other = [x for x in range(K)
                         if ring[x % npart] != srv.node_id]
                done, aborts, secs = run_mix(
                    srv.api, req["seed"], req["txns"], own, other,
                    req.get("cross", 0.1),
                    threads=req.get("threads", 4))
                if prof is not None:
                    import pstats

                    prof.disable()
                    pstats.Stats(prof, stream=sys.stderr).sort_stats(
                        "cumulative").print_stats(14)
                    sys.stderr.flush()
                out({"txns": done, "secs": secs, "aborts": aborts})
            elif cmd == "rpc_timing":
                # wrap the fabric handler: per-method service times of
                # every partition RPC this node answers
                import collections

                times = collections.defaultdict(list)
                orig = srv._handle

                def timed(origin, kind, payload, _o=orig):
                    if kind != "part":
                        return _o(origin, kind, payload)
                    t0 = time.perf_counter()
                    try:
                        return _o(origin, kind, payload)
                    finally:
                        times[payload[1]].append(
                            time.perf_counter() - t0)

                srv._handle_timed = timed
                srv.link._handler = timed
                srv._rpc_times = times
                out({"ok": True})
            elif cmd == "rpc_dump":
                import numpy as _np

                rep = {}
                for m, ts in srv._rpc_times.items():
                    a = _np.array(ts) * 1e3
                    rep[m] = {
                        "n": len(a),
                        "p50": round(float(_np.percentile(a, 50)), 2),
                        "p90": round(float(_np.percentile(a, 90)), 2),
                        "p99": round(float(_np.percentile(a, 99)), 2),
                        "sum_ms": round(float(a.sum())),
                    }
                    ts.clear()
                out({"ok": True, "rpc": rep})
            elif cmd == "exit":
                srv.close()
                out({"ok": True})
                return
        except Exception as e:  # noqa: BLE001
            out({"error": f"{type(e).__name__}: {e}"})


if __name__ == "__main__":
    main()
