"""Batched data-plane benchmark on the simulated fabric: the
doorbell-batching paths (qpush_batch vs per-WR qpush, lookup_many vs
per-key lookup) and the notify-driven single-op wait.

Emits ``BENCH_batched_lookup.json`` (repo root by default):

    PYTHONPATH=src python -m benchmarks.batched_lookup
    PYTHONPATH=src python -m benchmarks.batched_lookup --smoke   # tiny

Times are on the simulated microsecond clock, not wall clock.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
DEFAULT_OUT = os.path.join(REPO_ROOT, "BENCH_batched_lookup.json")


# ------------------------------------------------------- fabric doorbells
def bench_fabric_batching(n_wrs=256, signal_interval=16) -> Dict:
    """Three generations of the same 64B-READ batch on the simulated
    fabric: per-WR raw push (one syscall + doorbell + CQE each), raw
    qpush_batch (the hand-rolled batch discipline), and the Session layer
    (typed futures, auto-planned batching). The raw paths go through the
    deprecated ``repro.core.legacy`` shims — they ARE the deprecated
    idiom — and the session-vs-raw delta is the overhead the session
    abstraction costs (gated <= 5% at batch >= 128 in run.py --smoke)."""
    from repro.core import WorkRequest, connect, legacy, make_cluster

    def run(mode: str) -> float:
        cluster = make_cluster(n_nodes=2, n_meta=1)
        env = cluster.env
        m0, m1 = cluster.module("n0"), cluster.module("n1")
        out = {}

        def scenario():
            mr_srv = yield from m1.sys_qreg_mr(4096)
            t0 = None
            if mode == "session":
                sess = yield from connect(m0, "n1",
                                          signal_interval=signal_interval)
                # warm (MRStore + pool growth), mirroring the raw warmup
                yield from sess.read(mr_srv.rkey, 0, 64).wait()
                t0 = env.now
                with sess.batch():
                    futs = [sess.read(mr_srv.rkey, 0, 64)
                            for _ in range(n_wrs)]
                yield from sess.wait_all(futs)
            else:
                mr = yield from m0.sys_qreg_mr(4096)
                qd = yield from m0.sys_queue()
                yield from m0.sys_qconnect(qd, "n1")

                def wrs():
                    return [WorkRequest(op="READ", wr_id=i, local_mr=mr,
                                        local_off=0,
                                        remote_rkey=mr_srv.rkey,
                                        remote_off=0, nbytes=64)
                            for i in range(n_wrs)]

                # warm the MRStore so every mode times the same fast path
                rc = yield from legacy.qpush(m0, qd, wrs()[:1])
                assert rc == 0
                yield from legacy.qpop_block(m0, qd)
                t0 = env.now
                if mode == "batched":
                    n_cqes = yield from legacy.qpush_batch(
                        m0, qd, wrs(), signal_interval=signal_interval)
                    yield from legacy.qpop_batch_block(m0, qd, n_cqes)
                else:
                    for wr in wrs():
                        rc = yield from legacy.qpush(m0, qd, [wr])
                        assert rc == 0
                        yield from legacy.qpop_block(m0, qd)
            out["us"] = env.now - t0
            return True

        env.run_process(scenario(), "s")
        return out["us"]

    per_op, batched, session = run("per_op"), run("batched"), run("session")
    return {"n_wrs": n_wrs, "signal_interval": signal_interval,
            "per_op_us": round(per_op, 2), "batched_us": round(batched, 2),
            "session_us": round(session, 2),
            "per_op_us_per_wr": round(per_op / n_wrs, 3),
            "batched_us_per_wr": round(batched / n_wrs, 3),
            "session_us_per_wr": round(session / n_wrs, 3),
            "session_overhead": round(session / batched - 1.0, 4),
            "speedup": round(per_op / batched, 2),
            "session_speedup": round(per_op / session, 2)}


def bench_notify_single_op(n_ops=64) -> Dict:
    """Notify-driven completion vs the polled baseline, single-op regime.

    The batched paths amortize the poll charge across a doorbell batch;
    a latency-sensitive single-op caller cannot. This bench pins the two
    sides of the event-driven reactor redesign:

    * **latency**: p50 of a single 64B READ through the session (reactor
      blocks on the QP's completion-notify edge, wakes AT the CQE
      instant) must be no worse than the deprecated polled idiom
      (``qpop_block`` spinning 0.2us ticks);
    * **idle syscalls**: a blocked single-op caller — one READ, and one
      two-sided ``call`` parked on a listener round trip — must issue
      ZERO unproductive pops (``Session.stat_idle_polls``).

    Both are gated in ``run.py --smoke``.
    """
    from repro.core import WorkRequest, connect, legacy, listen, \
        make_cluster

    # ---- polled baseline: deprecated per-op qpush + qpop_block spin
    cluster = make_cluster(n_nodes=2, n_meta=1)
    env = cluster.env
    m0, m1 = cluster.module("n0"), cluster.module("n1")
    out: Dict = {}

    def polled():
        mr_srv = yield from m1.sys_qreg_mr(4096)
        mr = yield from m0.sys_qreg_mr(4096)
        qd = yield from m0.sys_queue()
        yield from m0.sys_qconnect(qd, "n1")

        def wr():
            return [WorkRequest(op="READ", wr_id=1, local_mr=mr,
                                local_off=0, remote_rkey=mr_srv.rkey,
                                remote_off=0, nbytes=64)]

        rc = yield from legacy.qpush(m0, qd, wr())       # warm MRStore
        assert rc == 0
        yield from legacy.qpop_block(m0, qd)
        lats = []
        for _ in range(n_ops):
            t0 = env.now
            rc = yield from legacy.qpush(m0, qd, wr())
            assert rc == 0
            yield from legacy.qpop_block(m0, qd)
            lats.append(env.now - t0)
        out["polled"] = lats
        return True

    env.run_process(polled(), "polled")

    # ---- notify-driven session path (same shape, fresh cluster)
    cluster = make_cluster(n_nodes=2, n_meta=1)
    env = cluster.env
    m0, m1 = cluster.module("n0"), cluster.module("n1")

    def notify():
        mr_srv = yield from m1.sys_qreg_mr(4096)
        sess = yield from connect(m0, "n1")
        yield from sess.read(mr_srv.rkey, 0, 64).wait()  # warm
        sess.stat_idle_polls = 0
        lats = []
        for _ in range(n_ops):
            t0 = env.now
            yield from sess.read(mr_srv.rkey, 0, 64).wait()
            lats.append(env.now - t0)
        out["notify"] = lats
        out["read_idle_polls"] = sess.stat_idle_polls
        out["notify_blocks"] = sess.stat_notify_blocks
        return True

    env.run_process(notify(), "notify")

    # ---- blocked two-sided call: park on a listener round trip
    cluster = make_cluster(n_nodes=2, n_meta=1)
    env = cluster.env
    m0, m1 = cluster.module("n0"), cluster.module("n1")

    def echo_server():
        lst = yield from listen(m1, 8901, msg_bytes=1024, window=4)
        msgs = yield from lst.recv()
        yield from msgs[0].reply(msgs[0].payload)
        return True

    def blocked_call():
        sess = yield from connect(m0, "n1", port=8901)
        fut = sess.call(b"ping", deadline_us=50_000.0)
        yield from fut.wait()
        out["call_idle_polls"] = sess.stat_idle_polls
        return True

    sp = env.process(echo_server(), "srv")
    cp = env.process(blocked_call(), "cli")
    env.run()
    assert sp.triggered and cp.triggered

    polled_p50 = float(np.percentile(out["polled"], 50))
    notify_p50 = float(np.percentile(out["notify"], 50))
    return {"n_ops": n_ops,
            "polled_p50_us": round(polled_p50, 3),
            "notify_p50_us": round(notify_p50, 3),
            "speedup": round(polled_p50 / notify_p50, 3),
            "read_idle_polls": int(out["read_idle_polls"]),
            "call_idle_polls": int(out["call_idle_polls"]),
            "notify_blocks": int(out["notify_blocks"])}


def bench_kv_batching(n_keys=48) -> Dict:
    """RaceClient.lookup_many vs per-key lookup on the simulated fabric."""
    from repro.core import make_cluster
    from repro.kvs import RaceKVStore
    from repro.kvs.race import RaceClient

    cluster = make_cluster(n_nodes=2, n_meta=1)
    env = cluster.env
    store = RaceKVStore(cluster.node("n1"), n_buckets=1024)
    for k in range(1, 2 * n_keys + 1):
        store.insert(k, b"v")
    client = RaceClient(cluster.module("n0"), store)
    out = {}

    def scenario():
        yield from client.bootstrap()
        keys = list(range(1, n_keys + 1))
        t0 = env.now
        vals = yield from client.lookup_many(keys)
        out["batched"] = env.now - t0
        assert all(v == b"v" for v in vals)
        t0 = env.now
        for k in keys:
            v = yield from client.lookup(k)
            assert v == b"v"
        out["per_key"] = env.now - t0
        return True

    env.run_process(scenario(), "s")
    return {"n_keys": n_keys,
            "per_op_us": round(out["per_key"], 2),
            "batched_us": round(out["batched"], 2),
            "per_op_us_per_key": round(out["per_key"] / n_keys, 3),
            "batched_us_per_key": round(out["batched"] / n_keys, 3),
            "speedup": round(out["per_key"] / out["batched"], 2)}


# ------------------------------------------------------------------- main
def run_suite(smoke: bool = False) -> Dict:
    if smoke:
        # n_wrs=128: the session-overhead gate is defined at batch >= 128
        fabric = bench_fabric_batching(n_wrs=128, signal_interval=8)
        kv = bench_kv_batching(n_keys=8)
        notify = bench_notify_single_op(n_ops=16)
    else:
        fabric = bench_fabric_batching()
        kv = bench_kv_batching()
        notify = bench_notify_single_op()
    return {"fabric_qpush_batch": fabric,
            "kv_lookup_many": kv, "notify_single_op": notify}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help=f"output JSON (default: {DEFAULT_OUT}; smoke "
                         f"runs default to a separate _smoke file so they "
                         f"never clobber the full artifact)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, 1 repeat (CI without TPU)")
    args = ap.parse_args()
    if args.out is None:
        args.out = DEFAULT_OUT.replace(".json", "_smoke.json") \
            if args.smoke else DEFAULT_OUT
    results = run_suite(smoke=args.smoke)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    fb = results["fabric_qpush_batch"]
    print(f"fabric qpush_batch n={fb['n_wrs']} "
          f"per-op={fb['per_op_us_per_wr']}us/wr "
          f"batched={fb['batched_us_per_wr']}us/wr "
          f"session={fb['session_us_per_wr']}us/wr "
          f"(overhead {100 * fb['session_overhead']:.1f}%) "
          f"speedup={fb['speedup']}x")
    kv = results["kv_lookup_many"]
    print(f"kv lookup_many n={kv['n_keys']} speedup={kv['speedup']}x")
    ns = results["notify_single_op"]
    print(f"notify single-op p50 polled={ns['polled_p50_us']}us "
          f"notify={ns['notify_p50_us']}us ({ns['speedup']}x), "
          f"idle_polls read={ns['read_idle_polls']} "
          f"call={ns['call_idle_polls']}")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
