"""The device tables' host hashing as whole-array numpy (``prep_keys``,
``shard_of_keys``) against its definition, the scalar hashes ``_fp``,
``_h1``, ``_h2`` and ``shard_of_key`` on Python integers: equal element
for element on random and edge keys of every integer dtype, and the
sharded table's queries land where its per-key ``insert`` wrote."""

import numpy as np
import pytest

from repro.kvs.race import (ShardedDeviceRaceTable, _fp, _h1, _h2,
                            prep_keys, shard_of_key, shard_of_keys)

SEED = 2 ** 33 + 11
EDGE = [0, 1, 2 ** 31 - 1, 2 ** 32, 2 ** 62, 2 ** 63 - 1, -1, -2 ** 63]


def _random(dtype):
    info = np.iinfo(dtype)
    return np.random.default_rng(SEED).integers(
        info.min, info.max, 4096, dtype=dtype, endpoint=True)


KEYS = {
    "random-int64": _random(np.int64),
    "random-uint64": _random(np.uint64),
    "random-int32": _random(np.int32),
    "random-list": _random(np.int64).tolist(),
    "edge-int64": np.array(EDGE, np.int64),
    "edge-list": EDGE,
    "edge-uint64": np.array([k for k in EDGE if k >= 0] + [2 ** 64 - 1],
                            np.uint64),
    "empty-int64": np.array([], np.int64),
    "empty-list": [],
}


@pytest.mark.parametrize("n_buckets", [7, 32, 1021, 262139, 2083339])
@pytest.mark.parametrize("name", list(KEYS))
def test_array_hashes_equal_the_scalar_hashes(name, n_buckets):
    keys = KEYS[name]
    ints = [int(k) for k in keys]
    fps, bidx = prep_keys(keys, n_buckets)
    assert fps.dtype == bidx.dtype == np.int32
    assert fps.shape == (len(ints),) and bidx.shape == (len(ints), 2)
    assert fps.tolist() == [(_fp(k) & 0x7FFFFFFF) or 1 for k in ints]
    assert bidx.tolist() == [[_h1(k, n_buckets), _h2(k, n_buckets)]
                             for k in ints]
    for n_shards in (4, 251):
        sidx = shard_of_keys(keys, n_shards)
        assert sidx.dtype == np.int32
        assert sidx.tolist() == [shard_of_key(k, n_shards) for k in ints]


@pytest.mark.parametrize("keys", [np.array([1.0, 2.0]),
                                  np.array([1, 2], object)],
                         ids=["float", "object"])
def test_non_integer_keys_are_refused(keys):
    with pytest.raises(TypeError):
        prep_keys(keys, 1021)
    with pytest.raises(TypeError):
        shard_of_keys(keys, 251)


def test_sharded_queries_find_each_key_where_insert_wrote_it():
    table = ShardedDeviceRaceTable(n_shards=4, n_buckets=32, nslot=8,
                                   vdim=4)
    rng = np.random.default_rng(SEED)
    keys = rng.choice(2 ** 31, 300, replace=False)
    vals = rng.standard_normal((300, 4)).astype(np.float32)
    for k, v in zip(keys.tolist(), vals):
        table.insert(k, v)
    fps, bidx, sidx = table.prep(keys)
    assert sidx.tolist() == [table.shard_of(k) for k in keys.tolist()]
    fp, val = table.tables()
    for i in range(len(keys)):
        rows = fp[sidx[i], bidx[i]]                      # (2, NSLOT)
        hits = np.argwhere(rows == fps[i])
        assert len(hits) >= 1
        got = [val[sidx[i], bidx[i][c], s] for c, s in hits]
        assert any(np.array_equal(g, vals[i]) for g in got)
