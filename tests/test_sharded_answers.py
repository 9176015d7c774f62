"""The sharded table's answers are put in the keys' order on the device:
``race_lookup_pallas_sharded`` groups the keys per shard, runs
``sharded_lookup_call`` on the padded groups and gathers the padded
answers back by each key's slot, all without a copy to the host. CPU,
tiny tables (Pallas in interpret mode)."""

from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.race_lookup.race_lookup import (_group_slots,
                                                   group_by_shard,
                                                   sharded_lookup_call)
from repro.kvs.race import ShardedDeviceRaceTable

NS, NB, NSLOT, VDIM = 3, 37, 8, 32
QBLOCK = 64


@pytest.fixture(scope="module")
def table():
    t = ShardedDeviceRaceTable(n_shards=NS, n_buckets=NB, nslot=NSLOT,
                               vdim=VDIM)
    rng = np.random.default_rng(13)
    keys = rng.choice(np.arange(1, 10_000), 300, replace=False)
    for k, v in zip(keys.tolist(),
                    rng.standard_normal((300, VDIM)).astype(np.float32)):
        t.insert(k, v)
    t.loaded = np.sort(keys)
    return t


def _absent(table, n):
    return np.setdiff1d(np.arange(10_000, 10_000 + 4 * n), table.loaded)[:n]


def _in_shard(table, keys, shard):
    return np.array([k for k in keys.tolist() if table.shard_of(k) == shard])


def _batch(table, case):
    loaded = table.loaded
    if case == "duplicates":
        a, b, c = loaded[:3]
        return np.array([a, a, b, a, *_absent(table, 2), c, c, b, 9_999_999])
    if case == "one_shard":
        return np.concatenate([_in_shard(table, loaded, 1)[:20],
                               _in_shard(table, _absent(table, 60), 1)])
    if case == "all_misses":
        return _absent(table, 50)
    if case == "single":
        return loaded[7:8]
    if case == "shuffled_tiles":
        # over QBLOCK keys a shard, in no shard's order: QCAP spans tiles
        keys = np.concatenate([loaded[:200], _absent(table, 50)])
        return np.random.default_rng(3).permutation(keys)
    assert case == "one_tile"
    # QBLOCK keys in every shard: QCAP is one tile and no slot is padding
    pool = np.concatenate([loaded, _absent(table, 600)])
    return np.concatenate([_in_shard(table, pool, s)[:QBLOCK]
                           for s in range(NS)])


def _bits(a):
    return np.ascontiguousarray(a, np.float32).view(np.uint32)


@pytest.mark.parametrize("case", ["duplicates", "one_shard", "all_misses",
                                  "single", "one_tile", "shuffled_tiles"])
def test_sharded_answers_equal_the_reference_bit_for_bit(table, case):
    keys = _batch(table, case)
    if case == "one_tile":
        _, _, sidx = table.prep(keys)
        _, _, pos, qblock = group_by_shard(
            np.zeros(len(keys), np.int32), np.zeros((len(keys), 2), np.int32),
            sidx, NS, QBLOCK)
        assert pos.shape == (NS, QBLOCK) and qblock == QBLOCK
        assert (pos >= 0).all()
    if case == "shuffled_tiles":
        _, _, sidx = table.prep(keys)
        assert np.bincount(sidx).max() > QBLOCK
    v, f = (np.asarray(a) for a in table.lookup_batch(keys, impl="pallas"))
    rv, rf = (np.asarray(a) for a in table.lookup_batch(keys, impl="ref"))
    assert v.shape == (len(keys), VDIM) and f.shape == (len(keys),)
    assert (f == rf).all() and (_bits(v) == _bits(rv)).all()
    assert (f != 0).tolist() == [k in set(table.loaded.tolist())
                                 for k in keys.tolist()]
    if case == "all_misses":
        assert not f.any() and not v.any()


@pytest.mark.parametrize("impl", ["pallas", "ref"])
def test_lookup_batch_returns_device_arrays(table, impl):
    v, f = table.lookup_batch(table.loaded[:40], impl=impl)
    assert isinstance(v, jax.Array) and isinstance(f, jax.Array)
    assert v.dtype == jnp.float32 and f.dtype == jnp.int32


def test_each_key_s_slot_holds_that_key():
    rng = np.random.default_rng(5)
    sidx = rng.integers(0, NS, 200)
    q = rng.integers(1, 2 ** 31, 200).astype(np.int32)
    b = rng.integers(0, NB, (200, 2)).astype(np.int32)
    q_g, b_g, inv, qblock = _group_slots(q, b, sidx, NS, QBLOCK)
    assert inv.dtype == np.int32 and inv.shape == (200,)
    assert (q_g.reshape(-1)[inv] == q).all()
    assert (b_g.reshape(-1, 2)[inv] == b).all()
    assert (inv // q_g.shape[1] == sidx).all()
    _, _, pos, qb = group_by_shard(q, b, sidx, NS, QBLOCK)
    assert qb == qblock and (pos.reshape(-1)[inv] == np.arange(200)).all()
    assert (pos >= 0).sum() == 200


def _race_spans(tmp_path, fn):
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = next(Path(tmp_path).rglob("*.xplane.pb"))
    events = [(e.start_ns, e.name, dict(e.stats))
              for plane in jax.profiler.ProfileData.from_file(
                  str(path)).planes if plane.name == "/host:CPU"
              for line in plane.lines for e in line.events
              if e.name.startswith("race.")]
    return [(n, a) for _, n, a in sorted(events, key=lambda x: x[0])]


def test_a_resident_call_copies_nothing_back(table, tmp_path):
    keys = table.loaded[10:90]
    table.lookup_batch(keys)                # the table is resident now
    h2d = table.stats.h2d_bytes
    spans = _race_spans(tmp_path, lambda: table.lookup_batch(keys))
    assert [n for n, _ in spans] == ["race.prep", "race.stack",
                                     "race.group", "race.to_device",
                                     "race.kernel"]
    group, to_device = spans[2][1], spans[3][1]
    # the grouped queries and bucket rows, and one int32 slot a key
    assert to_device["h2d_bytes"] == group["slots"] * (4 + 8) + len(keys) * 4
    assert table.stats.h2d_bytes - h2d == to_device["h2d_bytes"]


def test_the_warm_up_s_kernel_call_serves_the_lookup_path(table):
    keys = table.loaded[:50]
    _, _, sidx = table.prep(keys)
    q_g, _, _, qblock = group_by_shard(
        np.zeros(len(keys), np.int32), np.zeros((len(keys), 2), np.int32),
        sidx, NS, QBLOCK)
    qcap = q_g.shape[1]
    fp, val = table.shards[0].tables()
    fp_t = jnp.zeros((NS, *fp.shape), fp.dtype)
    val_t = jnp.zeros((NS, *val.shape), val.dtype)
    out = sharded_lookup_call(
        fp_t, val_t, jnp.zeros((NS, qcap), jnp.int32),
        jnp.zeros((NS, qcap, 2), jnp.int32), qblock=qblock, interpret=None)
    assert len(out) == 2
    for o in out:
        o.block_until_ready()
    assert out[0].shape == (NS, qcap, VDIM) and out[1].shape == (NS, qcap)
    assert not np.asarray(out[1]).any()
    # the lookup path dispatches the very executable the warm-up made
    compiled = sharded_lookup_call._cache_size()
    v, f = table.lookup_batch(keys)
    assert np.asarray(f).all()
    assert sharded_lookup_call._cache_size() == compiled
