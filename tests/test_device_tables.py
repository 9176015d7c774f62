"""The contract every device RACE table keeps, whichever layout it holds
and whichever ``impl`` serves it: ``DeviceRaceTable`` (flat),
``ShardedDeviceRaceTable`` (the dkv shard map), ``PoolRaceTable``
(RACE's slots over a pool of KV blocks) and ``MeshPoolRaceTable`` (that
pool over four memory nodes, here all on one device), each with its kernel
(``"pallas"``) and its pure-jnp oracle (``"ref"``). Every answer is held
to a plain dictionary of the inserted records, bit for bit. CPU, tiny
tables (Pallas in interpret mode)."""

import numpy as np
import pytest

from repro.kvs.race import (DeviceRaceTable, MeshPoolRaceTable,
                            PoolRaceTable, ShardedDeviceRaceTable)

VDIM = 32
N_RECORDS = 300
TABLES = ["flat", "sharded", "pool", "pool_mesh"]


def _table(kind):
    if kind == "flat":
        return DeviceRaceTable(n_buckets=101, nslot=8, vdim=VDIM)
    if kind == "sharded":
        return ShardedDeviceRaceTable(n_shards=3, n_buckets=37, nslot=8,
                                      vdim=VDIM)
    if kind == "pool":
        return PoolRaceTable(n_buckets=101, nslot=8, vdim=VDIM, capacity=400)
    return MeshPoolRaceTable(n_nodes=4, n_buckets=37, nslot=8, vdim=VDIM,
                             capacity=120)


def _records(seed=1):
    rng = np.random.default_rng(seed)
    keys = rng.choice(2 ** 30, N_RECORDS, replace=False) + 1
    return keys, rng.standard_normal((N_RECORDS, VDIM)).astype(np.float32)


def _load(table, keys, vals):
    if isinstance(table, (PoolRaceTable, MeshPoolRaceTable)):
        table.insert_many(keys, vals)
    else:
        for k, v in zip(keys.tolist(), vals):
            table.insert(k, v)


def _bits(a):
    return np.ascontiguousarray(a, np.float32).view(np.uint32)


def _expect(keys, vals, asked):
    """What a plain dictionary of the inserted records answers."""
    record = dict(zip(keys.tolist(), vals))
    found = np.array([k in record for k in asked.tolist()], np.int32)
    want = np.zeros((len(asked), VDIM), np.float32)
    for i, k in enumerate(asked.tolist()):
        if k in record:
            want[i] = record[k]
    return want, found


def _absent(n, seed=2):
    return np.random.default_rng(seed).integers(2 ** 30 + 1, 2 ** 31 - 1, n)


def _empty_batch(keys):
    return np.zeros(0, np.int64)


def _mixed_ragged(keys):
    """77 keys: loaded ones, absent ones, and loaded ones asked twice."""
    asked = np.concatenate([keys[:50:2], _absent(30), keys[:22]])
    np.random.default_rng(3).shuffle(asked)
    return asked


def _one_key(keys):
    return keys[7:8]


@pytest.mark.parametrize("impl", ["pallas", "ref"])
@pytest.mark.parametrize("kind", TABLES)
@pytest.mark.parametrize("case", ["empty_batch", "empty_table",
                                  "mixed_ragged", "one_key"])
def test_a_table_answers_as_its_records(kind, impl, case):
    table = _table(kind)
    keys, vals = _records()
    if case == "empty_table":
        asked = np.arange(1, 70)
        keys, vals = keys[:0], vals[:0]
    else:
        _load(table, keys, vals)
        asked = {"empty_batch": _empty_batch, "mixed_ragged": _mixed_ragged,
                 "one_key": _one_key}[case](keys)
    v, f = (np.asarray(a) for a in table.lookup_batch(asked, impl=impl))
    assert v.shape == (len(asked), VDIM) and v.dtype == np.float32
    assert f.shape == (len(asked),) and f.dtype == np.int32
    want, found = _expect(keys, vals, asked)
    np.testing.assert_array_equal(f, found)
    np.testing.assert_array_equal(_bits(v), _bits(want))
    if case == "mixed_ragged":
        assert len(asked) == 77 and 0 < f.sum() < len(asked)
    if case == "empty_table" and kind.startswith("pool"):
        assert int(table.stats.blocks) == 0     # no slot matched
    assert table.stats.calls == 1 and table.stats.keys == len(asked)


@pytest.mark.parametrize("kind", TABLES)
def test_an_unknown_impl_is_refused(kind):
    table = _table(kind)
    keys, vals = _records()
    _load(table, keys[:20], vals[:20])
    with pytest.raises(ValueError, match="unknown impl"):
        table.lookup_batch(keys[:5], impl="scalar")
