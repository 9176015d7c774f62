"""Batched data-plane invariants (qpush_batch / qpop_batch / get_many /
lookup_many / flat-table race-lookup kernel) plus regression tests for the
pool.decay and QP.reset_from_error fixes."""

import math

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import WorkRequest, make_cluster
from repro.core.qp import QP, QPError, QPState, QPType
from repro.core.pool import HybridQPPool
from repro.kvs import RaceKVStore
from repro.kvs.race import RaceClient


def build_cluster(n_nodes=2):
    return make_cluster(n_nodes=n_nodes, n_meta=1)


def _read_wrs(mr, mr_srv, n, nbytes=8):
    return [WorkRequest(op="READ", wr_id=1000 + i, local_mr=mr,
                        local_off=0, remote_rkey=mr_srv.rkey,
                        remote_off=0, nbytes=nbytes)
            for i in range(n)]


# ================================================== qpush_batch invariants
@st.composite
def batch_config(draw):
    n = draw(st.integers(1, 120))
    sq_depth = draw(st.integers(4, 48))
    cq_depth = draw(st.integers(4, 48))
    interval = draw(st.integers(1, 24))
    return n, sq_depth, cq_depth, interval


@settings(max_examples=25, deadline=None)
@given(batch_config())
def test_qpush_batch_never_overflows_and_cqe_count_exact(cfg):
    """At ANY (batch size, sq_depth, cq_depth, signal_interval):

    * no SQ overflow / CQ overrun (the QP stays RTS),
    * qpush_batch of N WRs generates exactly ceil(N / interval_eff) CQEs
      (interval clamped to min(sq_depth, cq_depth - 1)),
    * covers accounting retires every SQ entry (occupancy returns to 0 and
      vq.uncomp_cnt to 0 after the drain).
    """
    n, sq_depth, cq_depth, interval = cfg
    cluster = build_cluster()
    m0, m1 = cluster.module("n0"), cluster.module("n1")
    for qp in m0.pools[0].dc_qps:
        qp.sq_depth, qp.cq_depth = sq_depth, cq_depth

    def scenario():
        mr_srv = yield from m1.sys_qreg_mr(4096)
        mr = yield from m0.sys_qreg_mr(4096)
        qd = yield from m0.sys_queue()
        yield from m0.sys_qconnect(qd, "n1")
        vq = m0.vqs[qd]
        n_cqes = yield from m0.qpush_batch(
            qd, _read_wrs(mr, mr_srv, n), signal_interval=interval)
        k_eff = min(interval, min(sq_depth, cq_depth - 1))
        assert n_cqes == math.ceil(n / k_eff), (n_cqes, n, k_eff)
        ents = yield from m0.qpop_batch_block(qd, n_cqes)
        assert len(ents) == n_cqes
        assert sum(e.covers for e in ents) == n
        assert not any(e.err for e in ents)
        assert vq.uncomp_cnt == 0
        # no spurious extra completions
        extra = yield from m0.qpop_batch(qd, max_n=16)
        assert extra == []
        assert vq.qp.sq_occupancy == 0
        return True

    assert cluster.env.run_process(scenario(), "s")
    for qp in m0.pools[0].dc_qps:
        assert qp.state == QPState.RTS


def test_qpush_batch_covers_matches_per_wr_path():
    """The same signaling pattern pushed via sys_qpush (caller-set flags)
    and via qpush_batch must produce identical covers sequences."""
    n, k = 40, 7

    def run(batched):
        cluster = build_cluster()
        m0, m1 = cluster.module("n0"), cluster.module("n1")
        out = {}

        def scenario():
            mr_srv = yield from m1.sys_qreg_mr(4096)
            mr = yield from m0.sys_qreg_mr(4096)
            qd = yield from m0.sys_queue()
            yield from m0.sys_qconnect(qd, "n1")
            wrs = _read_wrs(mr, mr_srv, n)
            if batched:
                n_cqes = yield from m0.qpush_batch(qd, wrs,
                                                   signal_interval=k)
            else:
                for i, wr in enumerate(wrs):
                    wr.signaled = ((i + 1) % k == 0) or (i == n - 1)
                n_cqes = sum(w.signaled for w in wrs)
                rc = yield from m0.sys_qpush(qd, wrs)
                assert rc == 0
            ents = yield from m0.qpop_batch_block(qd, n_cqes)
            out["covers"] = [e.covers for e in ents]
            out["ids"] = [e.user_wr_id for e in ents]
            return True

        assert cluster.env.run_process(scenario(), "s")
        return out

    per_wr, batched = run(False), run(True)
    assert per_wr["covers"] == batched["covers"]
    assert per_wr["ids"] == batched["ids"]
    assert sum(batched["covers"]) == n


def test_qpop_batch_preserves_fifo_order():
    cluster = build_cluster()
    m0, m1 = cluster.module("n0"), cluster.module("n1")

    def scenario():
        mr_srv = yield from m1.sys_qreg_mr(4096)
        mr = yield from m0.sys_qreg_mr(4096)
        qd = yield from m0.sys_queue()
        yield from m0.sys_qconnect(qd, "n1")
        wrs = _read_wrs(mr, mr_srv, 30)
        n_cqes = yield from m0.qpush_batch(qd, wrs, signal_interval=5)
        ents = yield from m0.qpop_batch_block(qd, n_cqes)
        # every 5th user wr_id (the last WR, i=29, is also a 5th)
        assert [e.user_wr_id for e in ents] == \
            [1000 + i for i in range(4, 30, 5)]
        return True

    assert cluster.env.run_process(scenario(), "s")


def test_qpush_batch_rejects_atomically_across_segments():
    """A malformed WR in a LATER segment must reject the whole batch
    before anything is posted — no orphaned in-flight WRs or queued
    CompEntries from earlier segments."""
    cluster = build_cluster()
    m0, m1 = cluster.module("n0"), cluster.module("n1")
    for qp in m0.pools[0].dc_qps:
        qp.sq_depth, qp.cq_depth = 8, 8        # segment limit = 7

    def scenario():
        mr_srv = yield from m1.sys_qreg_mr(4096)
        mr = yield from m0.sys_qreg_mr(4096)
        qd = yield from m0.sys_queue()
        yield from m0.sys_qconnect(qd, "n1")
        vq = m0.vqs[qd]
        # warm the MRStore so the malformed batch's validation posts no
        # probe READs of its own, then compare post counts by delta
        n = yield from m0.qpush_batch(qd, _read_wrs(mr, mr_srv, 1))
        yield from m0.qpop_batch_block(qd, n)
        wrs = _read_wrs(mr, mr_srv, 20)
        wrs[15].op = "NOPE"                    # invalid, in segment 3
        posted_before = vq.qp.stat_posted
        rc = yield from m0.qpush_batch(qd, wrs, signal_interval=4)
        assert rc == -1
        assert vq.comp_queue == type(vq.comp_queue)()
        assert vq.uncomp_cnt == 0
        assert vq.qp.sq_occupancy == 0
        assert vq.qp.stat_posted == posted_before
        ent = yield from m0.sys_qpop(qd)
        assert ent is None
        return True

    assert cluster.env.run_process(scenario(), "s")
    assert all(qp.state == QPState.RTS for qp in m0.pools[0].dc_qps)


def test_qpush_batch_empty_and_invalid():
    cluster = build_cluster()
    m0, m1 = cluster.module("n0"), cluster.module("n1")

    def scenario():
        mr_srv = yield from m1.sys_qreg_mr(4096)
        mr = yield from m0.sys_qreg_mr(4096)
        qd = yield from m0.sys_queue()
        yield from m0.sys_qconnect(qd, "n1")
        n = yield from m0.qpush_batch(qd, [])
        assert n == 0
        bad = [WorkRequest(op="NOPE", wr_id=1, local_mr=mr,
                           remote_rkey=mr_srv.rkey, nbytes=8)]
        rc = yield from m0.qpush_batch(qd, bad)
        assert rc == -1
        # queue still healthy afterwards
        n = yield from m0.qpush_batch(qd, _read_wrs(mr, mr_srv, 3))
        assert n == 1
        ents = yield from m0.qpop_batch_block(qd, 1)
        assert sum(e.covers for e in ents) == 3
        return True

    assert cluster.env.run_process(scenario(), "s")
    assert all(qp.state == QPState.RTS for qp in m0.pools[0].dc_qps)


# ================================================ two-sided SEND batches
@st.composite
def send_batch_config(draw):
    n = draw(st.integers(1, 80))
    interval = draw(st.integers(1, 16))
    nbytes = draw(st.sampled_from([16, 256, 1024]))
    return n, interval, nbytes


@settings(max_examples=12, deadline=None)
@given(send_batch_config())
def test_send_batch_cqe_count_and_batched_recv_drain(cfg):
    """SEND batches through qpush_batch obey the SAME selective-signaling
    contract as the one-sided path — exactly ceil(N / interval_eff) CQEs,
    covers retiring every SQ entry — and the receiver drains all N
    messages through the batched recv pump + ONE-crossing sys_qpop_msgs,
    byte-exact and in FIFO order."""
    n, interval, nbytes = cfg
    cluster = build_cluster()
    m0, m1 = cluster.module("n0"), cluster.module("n1")
    env = cluster.env
    out = {}

    def server():
        qd = yield from m1.sys_queue()
        yield from m1.sys_qbind(qd, 6001)
        mr = yield from m1.sys_qreg_mr(n * nbytes + 4096)
        for i in range(n):
            yield from m1.sys_qpush_recv(qd, mr, i * nbytes, nbytes,
                                         wr_id=i)
        msgs = []
        spins = 0
        while len(msgs) < n:
            got = yield from m1.sys_qpop_msgs(qd, max_n=n)
            msgs.extend(got)
            if len(msgs) < n:
                spins += 1
                assert spins < 50_000, f"recv drain stalled at {len(msgs)}"
                yield env.timeout(1.0)
        out["msgs"], out["mr"] = msgs, mr
        return True

    def client():
        qd = yield from m0.sys_queue()
        yield from m0.sys_qconnect(qd, "n1", port=6001)
        mr = yield from m0.sys_qreg_mr(n * nbytes + 4096)
        buf = cluster.node("n0").buffer(mr.addr)
        for i in range(n):
            buf[i * nbytes: (i + 1) * nbytes] = (i * 37 + 11) % 251
        wrs = [WorkRequest(op="SEND", wr_id=3000 + i, local_mr=mr,
                           local_off=i * nbytes, nbytes=nbytes)
               for i in range(n)]
        vq = m0.vqs[qd]
        n_cqes = yield from m0.qpush_batch(qd, wrs,
                                           signal_interval=interval)
        qp = vq.qp
        k_eff = min(interval, min(qp.sq_depth, qp.cq_depth - 1))
        assert n_cqes == math.ceil(n / k_eff), (n_cqes, n, k_eff)
        ents = yield from m0.qpop_batch_block(qd, n_cqes)
        assert len(ents) == n_cqes
        assert sum(e.covers for e in ents) == n
        assert not any(e.err for e in ents)
        assert vq.uncomp_cnt == 0
        return True

    sp = env.process(server(), "srv")
    cp = env.process(client(), "cli")
    env.run()
    assert sp.triggered and cp.triggered
    msgs = out["msgs"]
    assert len(msgs) == n
    # FIFO: message i landed in recv buffer i with its own byte pattern
    assert [m.wr_id for m in msgs] == list(range(n))
    buf = cluster.node("n1").buffer(out["mr"].addr)
    for i in range(n):
        want = (i * 37 + 11) % 251
        got = buf[i * nbytes: (i + 1) * nbytes]
        assert (got == want).all(), (i, want, got[:4])


def test_send_batch_mostly_unsignaled_one_cqe():
    """A whole SEND batch with interval >= N produces exactly ONE CQE
    (the ROADMAP's 'mostly unsignaled' SEND regime) and still delivers
    every message."""
    n = 24
    cluster = build_cluster()
    m0, m1 = cluster.module("n0"), cluster.module("n1")
    env = cluster.env
    got = {}

    def server():
        qd = yield from m1.sys_queue()
        yield from m1.sys_qbind(qd, 6002)
        mr = yield from m1.sys_qreg_mr(1 << 16)
        for i in range(n):
            yield from m1.sys_qpush_recv(qd, mr, 64 * i, 64, wr_id=i)
        msgs = []
        while len(msgs) < n:
            msgs.extend((yield from m1.sys_qpop_msgs(qd)))
            if len(msgs) < n:
                yield env.timeout(1.0)
        got["n"] = len(msgs)
        return True

    def client():
        qd = yield from m0.sys_queue()
        yield from m0.sys_qconnect(qd, "n1", port=6002)
        mr = yield from m0.sys_qreg_mr(4096)
        wrs = [WorkRequest(op="SEND", wr_id=i, local_mr=mr, local_off=0,
                           nbytes=32) for i in range(n)]
        n_cqes = yield from m0.qpush_batch(qd, wrs, signal_interval=n)
        assert n_cqes == 1
        ents = yield from m0.qpop_batch_block(qd, 1)
        assert ents[0].covers == n and not ents[0].err
        return True

    sp = env.process(server(), "srv")
    cp = env.process(client(), "cli")
    env.run()
    assert sp.triggered and cp.triggered and got["n"] == n


# ================================= satellite: unsignaled-WR ERR routing
def test_unsignaled_err_cqes_route_to_owning_vq():
    """An ERR completion of an *unsignaled* WR must reach the owning
    VirtQueue (wr_ids now encode vq ownership with comp_cnt == 0), so a
    mostly-unsignaled SEND batch against a dead node surfaces an errored
    CompEntry instead of being dropped on the floor."""
    cluster = build_cluster()
    m0 = cluster.module("n0")
    env = cluster.env

    def scenario():
        qd = yield from m0.sys_queue()
        yield from m0.sys_qconnect(qd, "n1")
        vq = m0.vqs[qd]
        cluster.fabric.node("n1").alive = False
        wrs = [WorkRequest(op="SEND", wr_id=500 + i,
                           signaled=(i == 3),
                           payload=np.zeros(16, np.uint8), nbytes=16)
               for i in range(4)]
        rc = yield from m0.sys_qpush(qd, wrs)
        assert rc == 0
        ent = None
        for _ in range(10_000):            # bounded spin (no qpop_block:
            ent = yield from m0.sys_qpop(qd)   # a regression must not hang)
            if ent is not None:
                break
            yield env.timeout(0.5)
        assert ent is not None, "ERR completion never routed to owner vq"
        assert ent.err and vq.errored
        assert ent.covers == 4             # the whole run retires at once
        assert vq.uncomp_cnt == 0
        return True

    assert cluster.env.run_process(scenario(), "s")
    env.run()                              # let background recovery finish
    assert all(qp.state == QPState.RTS
               for qp in m0.pools[0].dc_qps)


# =========================================================== KV batching
def test_kvclient_get_many_with_collisions():
    cluster = build_cluster()
    m0 = cluster.module("n0")
    client = m0._meta_clients[0]
    kv = client.server
    # force collisions: occupy the probe-0 slots of some synthetic keys
    from repro.core.meta import fnv1a
    keys = [f"key{i}".encode() for i in range(24)]
    for k in keys:
        kv.put(k, b"val-" + k)
    # a missing key whose probe-0 slot is occupied (collision -> re-probe)
    missing = None
    occupied = {fnv1a(k) % kv.n_slots for k in keys}
    for i in range(10_000):
        cand = f"absent{i}".encode()
        if fnv1a(cand) % kv.n_slots in occupied:
            missing = cand
            break
    assert missing is not None

    def scenario():
        got = yield from client.get_many(keys + [missing, b"nothere"])
        for k, v in zip(keys, got[:len(keys)]):
            assert v == b"val-" + k
        assert got[len(keys)] is None        # collided then resolved miss
        assert got[len(keys) + 1] is None
        return True

    assert cluster.env.run_process(scenario(), "s")


def test_get_many_pipelines_rounds_behind_doorbells():
    """Satellite: with a scratch that forces many rounds, the pipelined
    get_many (round r+1 posted behind round r's doorbell, double-buffered
    scratch banks) must beat the serial per-chunk bound of one full RTT
    per round — while staying correct."""
    from repro.core.meta import KVClient

    cluster = build_cluster()
    m0 = cluster.module("n0")
    env = cluster.env
    base = m0._meta_clients[0]
    kv = base.server
    keys = [f"pipe{i}".encode() for i in range(40)]
    for k in keys:
        kv.put(k, b"pv-" + k[:8])
    # tiny scratch -> bank_cap 4, 10 pipelined rounds for 40 keys
    node = cluster.node("n0")
    scratch = node.reg_mr(node.alloc(8 * 32), 8 * 32)
    client = KVClient(base.qp, kv, scratch, scratch_off=0,
                      batch_scratch_off=0)

    def scenario():
        t0 = env.now
        v = yield from client.lookup(keys[0])
        rtt = env.now - t0
        assert v == b"pv-" + keys[0][:8]
        t0 = env.now
        got = yield from client.get_many(keys)
        elapsed = env.now - t0
        for k, v in zip(keys, got):
            assert v == b"pv-" + k[:8]
        n_rounds = 10
        # serial per-chunk sync costs ~one RTT per round; pipelining must
        # overlap at least a couple of rounds' worth
        assert elapsed < 0.8 * n_rounds * rtt, (elapsed, rtt)
        return True

    assert cluster.env.run_process(scenario(), "s")


def test_race_lookup_many_matches_per_key_and_is_faster():
    cluster = build_cluster()
    store = RaceKVStore(cluster.node("n1"), n_buckets=512)
    for k in range(1, 101):
        store.insert(k, f"v{k}".encode())
    client = RaceClient(cluster.module("n0"), store)
    env = cluster.env

    def scenario():
        yield from client.bootstrap()
        keys = list(range(1, 49)) + [7777, 8888]
        t0 = env.now
        batched = yield from client.lookup_many(keys)
        batched_us = env.now - t0
        t0 = env.now
        per_key = []
        for k in keys:
            v = yield from client.lookup(k)
            per_key.append(v)
        per_key_us = env.now - t0
        assert batched == per_key
        assert batched[0] == b"v1" and batched[-1] is None
        # one doorbell per chunk vs one per key: must be much cheaper
        assert batched_us < per_key_us / 2, (batched_us, per_key_us)
        return True

    assert env.run_process(scenario(), "s")


# ===================================================== satellite: pool fix
def test_decay_keeps_single_use_addresses_with_no_decay():
    fab_cluster = build_cluster()
    pool = HybridQPPool(fab_cluster.node("n0"), cpu=0)
    pool.use_counts = {"a": 1, "b": 4, "c": 2}
    pool.decay(factor=1.0)
    # count-1 addresses must survive a no-op decay (old code deleted them)
    assert pool.use_counts == {"a": 1, "b": 4, "c": 2}


def test_decay_drops_entries_only_when_decayed_to_zero():
    cluster = build_cluster()
    pool = HybridQPPool(cluster.node("n0"), cpu=0)
    pool.use_counts = {"a": 1, "b": 4, "c": 9}
    pool.decay(factor=0.5)
    # a: int(0.5)=0 dropped; b: 2; c: 4
    assert pool.use_counts == {"b": 2, "c": 4}
    # old code kept pre-decay n>1 entries even when they decayed to 0
    pool.use_counts = {"d": 4}
    pool.decay(factor=0.2)
    assert pool.use_counts == {}


# ============================================= satellite: reset_from_error
def test_reset_from_error_completes_after_recovery():
    """Regression: the old reset burned a seq to resync _next_complete,
    so the first WR posted after recovery could never complete (flush
    cursor waited forever on the burned seq)."""
    from tests.test_qp import make_pair, reg, rd

    fab, a, b, qa, _ = make_pair(sq_depth=4)
    la, rb = reg(a), reg(b)
    with pytest.raises(QPError):
        qa.post_send([rd(la, rb, wr_id=i) for i in range(5)])
    assert qa.state == QPState.ERR
    fab.env.run_process(qa.reset_from_error())
    assert qa.state == QPState.RTS
    qa.post_send([rd(la, rb, wr_id=42)])
    fab.env.run()
    cqes = qa.poll_cq(max_n=4)
    assert [c.wr_id for c in cqes] == [42]
    assert qa.sq_occupancy == 0


def test_reset_from_error_with_wr_in_flight():
    """A WR still in flight across the reset must neither stall the QP nor
    surface a stale completion afterwards."""
    from tests.test_qp import make_pair, reg, rd

    fab, a, b, qa, _ = make_pair(sq_depth=8)
    la, rb = reg(a), reg(b)
    qa.post_send([rd(la, rb, n=2048, wr_id=1)])   # slow WR, stays in flight
    qa._to_error("injected")
    fab.env.run_process(qa.reset_from_error())
    assert qa.state == QPState.RTS
    qa.post_send([rd(la, rb, wr_id=2)])
    fab.env.run()
    cqes = qa.poll_cq(max_n=8)
    # only the post-recovery WR completes; the stale one is dropped
    assert [c.wr_id for c in cqes] == [2]
    assert qa._done_buffer == {}


# ================================================ flat-table lookup kernel
@pytest.mark.parametrize("nq", [1, 7, 64, 65, 130])
def test_flat_kernel_ragged_batches_match_ref(nq):
    from repro.kernels.race_lookup.ops import race_lookup
    from repro.kernels.race_lookup.ref import make_table, race_lookup_ref

    rng = np.random.RandomState(nq * 31)
    nkeys, vdim = 150, 64
    keys = np.arange(1, nkeys + 1)
    vals = rng.randn(nkeys, vdim).astype(np.float32)
    fp, vt, prep = make_table(128, 8, vdim, keys, vals)
    qkeys = rng.randint(1, 2 * nkeys, nq)          # mix of hits and misses
    fps, bidx = prep(qkeys)
    v_k, f_k = race_lookup(fp, vt, fps, bidx, impl="pallas")
    v_r, f_r = race_lookup_ref(fp, vt, fps, bidx)
    np.testing.assert_array_equal(np.array(f_k), np.array(f_r))
    np.testing.assert_allclose(np.array(v_k), np.array(v_r), atol=1e-6)
