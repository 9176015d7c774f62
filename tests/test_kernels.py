"""Kernel validation: shape/dtype sweeps + hypothesis, vs ref.py oracles
(interpret mode executes the Pallas kernel bodies on CPU)."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.kernels.race_lookup.ops import race_lookup
from repro.kernels.race_lookup.ref import make_table, race_lookup_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.rwkv6.ops import wkv
from repro.kernels.rwkv6.ref import wkv_ref, wkv_sequential


# ------------------------------------------------------------ race lookup
@pytest.mark.parametrize("nb,nslot,vdim,nkeys", [
    (64, 8, 128, 200), (128, 4, 64, 100), (32, 16, 256, 300),
])
def test_race_lookup_sweep(nb, nslot, vdim, nkeys):
    rng = np.random.RandomState(nb)
    keys = np.arange(1, nkeys + 1)
    vals = rng.randn(nkeys, vdim).astype(np.float32)
    fp, vt, prep = make_table(nb, nslot, vdim, keys, vals)
    qkeys = np.concatenate([keys[:50], np.arange(10_000, 10_020)])
    fps, bidx = prep(qkeys)
    v_pal, f_pal = race_lookup(fp, vt, fps, bidx)
    v_ref, f_ref = race_lookup_ref(fp, vt, fps, bidx)
    np.testing.assert_array_equal(np.array(f_pal), np.array(f_ref))
    np.testing.assert_allclose(np.array(v_pal), np.array(v_ref), atol=1e-6)
    # present keys found with exact values, absent keys not found
    assert np.array(f_pal)[:50].all()
    assert not np.array(f_pal)[50:].any()
    np.testing.assert_allclose(np.array(v_pal)[:50], vals[:50], atol=1e-6)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 150), st.integers(0, 2 ** 20))
def test_race_lookup_hypothesis(nkeys, seed):
    rng = np.random.RandomState(seed % (2 ** 31))
    keys = rng.choice(np.arange(1, 10_000), size=nkeys, replace=False)
    vals = rng.randn(nkeys, 64).astype(np.float32)
    fp, vt, prep = make_table(256, 8, 64, keys, vals)
    qkeys = rng.choice(np.arange(1, 10_000), size=32)
    fps, bidx = prep(qkeys)
    v_pal, f_pal = race_lookup(fp, vt, fps, bidx)
    v_ref, f_ref = race_lookup_ref(fp, vt, fps, bidx)
    np.testing.assert_array_equal(np.array(f_pal), np.array(f_ref))
    np.testing.assert_allclose(np.array(v_pal), np.array(v_ref), atol=1e-6)


# --------------------------------------------------------- flash attention
@pytest.mark.parametrize("b,hq,hkv,s,d,causal,window,cap,dtype", [
    (2, 4, 2, 256, 64, True, None, None, np.float32),
    (1, 4, 4, 256, 64, True, 128, 50.0, np.float32),
    (1, 2, 1, 128, 32, False, None, None, np.float32),
    (1, 8, 2, 512, 64, True, None, 30.0, np.float32),
    (2, 2, 2, 256, 128, True, 64, None, np.float32),
    (1, 4, 2, 256, 64, True, None, None, jnp.bfloat16),
])
def test_flash_attention_sweep(b, hq, hkv, s, d, causal, window, cap,
                               dtype):
    rng = np.random.RandomState(0)
    q = (rng.randn(b, hq, s, d) * 0.5)
    k = (rng.randn(b, hkv, s, d) * 0.5)
    v = (rng.randn(b, hkv, s, d) * 0.5)
    q, k, v = (jnp.asarray(t, dtype) for t in (q, k, v))
    o_pal = flash_attention(q, k, v, causal=causal, window=window,
                            cap=cap, bq=64, bk=64)
    o_ref = flash_attention_ref(q, k, v, causal=causal, window=window,
                                cap=cap)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.array(o_pal, np.float32), np.array(o_ref, np.float32),
        atol=tol, rtol=tol)


def test_flash_attention_block_shape_independence():
    rng = np.random.RandomState(1)
    q = rng.randn(1, 2, 256, 64).astype(np.float32)
    k = rng.randn(1, 2, 256, 64).astype(np.float32)
    v = rng.randn(1, 2, 256, 64).astype(np.float32)
    o1 = flash_attention(q, k, v, bq=64, bk=64)
    o2 = flash_attention(q, k, v, bq=128, bk=32)
    np.testing.assert_allclose(np.array(o1), np.array(o2), atol=2e-5)


# ----------------------------------------------------------------- rwkv6
@pytest.mark.parametrize("b,h,s,dk,dv,chunk", [
    (2, 3, 128, 16, 16, 16), (1, 2, 64, 32, 32, 16),
    (1, 1, 256, 64, 64, 16), (2, 2, 96, 16, 32, 16),
])
def test_wkv_sweep(b, h, s, dk, dv, chunk):
    rng = np.random.RandomState(7)
    r = rng.randn(b, h, s, dk).astype(np.float32) * 0.4
    k = rng.randn(b, h, s, dk).astype(np.float32) * 0.4
    v = rng.randn(b, h, s, dv).astype(np.float32) * 0.4
    logw = np.clip(-np.exp(rng.randn(b, h, s, dk) * 0.3 - 0.6),
                   -4.25, -1e-6).astype(np.float32)
    u = (rng.randn(h, dk) * 0.3).astype(np.float32)
    o_pal = wkv(r, k, v, logw, u, chunk=chunk)
    o_seq = wkv_sequential(r, k, v, logw, u)
    np.testing.assert_allclose(np.array(o_pal), np.array(o_seq),
                               atol=5e-4, rtol=1e-3)


def test_wkv_chunked_jnp_matches_sequential_strong_decay():
    """Worst-case decays right at the clamp boundary stay finite/exact."""
    rng = np.random.RandomState(3)
    b, h, s, dk, dv = 1, 2, 64, 16, 16
    r = rng.randn(b, h, s, dk).astype(np.float32)
    k = rng.randn(b, h, s, dk).astype(np.float32)
    v = rng.randn(b, h, s, dv).astype(np.float32)
    logw = np.full((b, h, s, dk), -4.25, np.float32)
    u = np.zeros((h, dk), np.float32)
    o_ref = wkv_ref(r, k, v, logw, u)
    o_seq = wkv_sequential(r, k, v, logw, u)
    assert np.isfinite(np.array(o_ref)).all()
    np.testing.assert_allclose(np.array(o_ref), np.array(o_seq),
                               atol=5e-4, rtol=1e-3)


@pytest.mark.parametrize("ns,nb,nslot,vdim", [
    (3, 64, 8, 64), (2, 32, 4, 128), (5, 16, 8, 32),
])
def test_race_lookup_sharded_matches_per_shard_oracle(ns, nb, nslot, vdim):
    """Sharded kernel (grid dimension over shards, per-shard index map)
    vs the per-shard ref oracle — including ragged per-shard query counts
    and one shard with NO queries."""
    from repro.kernels.race_lookup.ops import race_lookup_sharded

    rng = np.random.RandomState(ns * nb)
    fps_t, vals_t, preps = [], [], []
    inserted = {}
    for s in range(ns):
        keys = rng.choice(np.arange(1, 5_000), size=nb * nslot // 4,
                          replace=False)
        vals = rng.randn(len(keys), vdim).astype(np.float32)
        fp, vt, prep = make_table(nb, nslot, vdim, keys, vals)
        fps_t.append(fp)
        vals_t.append(vt)
        preps.append(prep)
        inserted[s] = dict(zip((int(k) for k in keys), vals))
    fp_tables = np.stack(fps_t)
    val_tables = np.stack(vals_t)

    # ragged shard loads; shard 0 gets NO queries
    qkeys, qsidx = [], []
    for s in range(1, ns):
        n_s = 5 + 11 * s
        ks = rng.choice(np.arange(1, 5_000), size=n_s)
        qkeys.append(ks)
        qsidx.append(np.full(n_s, s))
    qkeys = np.concatenate(qkeys)
    qsidx = np.concatenate(qsidx).astype(np.int32)
    order = rng.permutation(len(qkeys))       # interleave shards
    qkeys, qsidx = qkeys[order], qsidx[order]

    fps = np.zeros(len(qkeys), np.int32)
    bidx = np.zeros((len(qkeys), 2), np.int32)
    for i, (k, s) in enumerate(zip(qkeys, qsidx)):
        f, b = preps[s](np.array([k]))
        fps[i] = f[0]
        bidx[i] = b[0]

    v_sh, f_sh = race_lookup_sharded(fp_tables, val_tables, fps, bidx,
                                     qsidx, impl="pallas", qblock=16)
    v_rf, f_rf = race_lookup_sharded(fp_tables, val_tables, fps, bidx,
                                     qsidx, impl="ref")
    np.testing.assert_array_equal(np.array(f_sh), np.array(f_rf))
    np.testing.assert_allclose(np.array(v_sh), np.array(v_rf), atol=1e-6)
    # ground truth: inserted keys found in THEIR shard's table only
    for i, (k, s) in enumerate(zip(qkeys, qsidx)):
        if int(k) in inserted[s]:
            assert np.array(f_rf)[i] == 1
            np.testing.assert_allclose(np.array(v_sh)[i],
                                       inserted[s][int(k)], atol=1e-6)


def test_race_lookup_sharded_empty_and_device_table():
    from repro.kernels.race_lookup.ops import race_lookup_sharded
    from repro.kvs.race import ShardedDeviceRaceTable

    fp = np.zeros((2, 8, 4), np.int32)
    vt = np.zeros((2, 8, 4, 16), np.float32)
    v, f = race_lookup_sharded(fp, vt, np.zeros(0, np.int32),
                               np.zeros((0, 2), np.int32),
                               np.zeros(0, np.int32))
    assert v.shape == (0, 16) and f.shape == (0,)

    table = ShardedDeviceRaceTable(n_shards=3, n_buckets=32, nslot=8,
                                   vdim=32)
    rng = np.random.RandomState(9)
    vals = {k: rng.randn(32).astype(np.float32) for k in range(1, 60)}
    for k, v_ in vals.items():
        table.insert(k, v_)
    qk = np.concatenate([np.arange(1, 60), np.arange(900, 910)])
    got_v, got_f = table.lookup_batch(qk, impl="pallas")
    ref_v, ref_f = table.lookup_batch(qk, impl="ref")
    np.testing.assert_array_equal(np.array(got_f), np.array(ref_f))
    np.testing.assert_allclose(np.array(got_v), np.array(ref_v), atol=1e-6)
    assert np.array(got_f)[:59].all() and not np.array(got_f)[59:].any()
    for i, k in enumerate(range(1, 60)):
        np.testing.assert_allclose(np.array(got_v)[i], vals[k], atol=1e-6)


def test_device_table_two_choice_loads_to_half():
    """The two candidate buckets are independent hashes: at load 0.5 no
    bucket overflows (with a second hash that is a function of the first,
    each key's two choices form a fixed pair and this load overflows)."""
    from repro.kvs.race import ShardedDeviceRaceTable

    table = ShardedDeviceRaceTable(n_shards=13, n_buckets=373, nslot=8,
                                   vdim=1)
    keys = np.random.default_rng(0).choice(2 ** 30, 20_000, replace=False)
    for k in keys.tolist():
        table.insert(k + 1, np.zeros(1, np.float32))
    loads = np.stack([s._loads for s in table.shards])
    assert loads.sum() == 20_000 and loads.max() <= 8
