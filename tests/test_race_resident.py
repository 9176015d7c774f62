"""Residency of the device RACE tables: the device copy of a table is
shipped once per mutation, not once per lookup, and a lookup after an
insert sees the insert. CPU, tiny tables (Pallas in interpret mode)."""

import numpy as np
import pytest

from repro.kernels.race_lookup.race_lookup import group_by_shard
from repro.kvs.race import DeviceRaceTable, ShardedDeviceRaceTable

VDIM = 32


def _table(kind):
    if kind == "flat":
        return DeviceRaceTable(n_buckets=64, nslot=8, vdim=VDIM)
    return ShardedDeviceRaceTable(n_shards=3, n_buckets=32, nslot=8,
                                  vdim=VDIM)


def _fill(table, keys, seed=0):
    rng = np.random.default_rng(seed)
    vals = {}
    for k in keys:
        vals[k] = rng.standard_normal(VDIM).astype(np.float32)
        table.insert(k, vals[k])
    return vals


def _table_bytes(table):
    return sum(a.nbytes for a in table.tables())


def _operand_bytes(table, keys):
    """Bytes a lookup of ``keys`` ships besides the table: the query
    operands (flat), or the grouped queries and each key's slot in the
    padded output (sharded)."""
    if isinstance(table, DeviceRaceTable):
        return len(keys) * (4 + 8)
    _, _, sidx = table.prep(keys)
    _, _, pos, _ = group_by_shard(np.zeros(len(keys), np.int32),
                                  np.zeros((len(keys), 2), np.int32),
                                  sidx, table.n_shards, 64)
    return pos.size * (4 + 8) + len(keys) * 4


def _bits(a):
    return np.ascontiguousarray(a, np.float32).view(np.uint32)


@pytest.mark.parametrize("kind", ["flat", "sharded"])
def test_lookups_ship_the_table_once(kind):
    table = _table(kind)
    vals = _fill(table, range(1, 120))
    batches = [np.arange(1, 60), np.arange(50, 150), np.arange(100, 101)]
    dev = None
    for b in batches:
        v, f = table.lookup_batch(b)
        if dev is None:
            dev = table._dev
        assert table._dev is dev            # the same device arrays
        f = np.asarray(f)
        for i, k in enumerate(b.tolist()):
            assert f[i] == (k in vals)
            if k in vals:
                assert (_bits(np.asarray(v)[i]) == _bits(vals[k])).all()
    assert table.stats.calls == 3 and table.stats.table_ships == 1
    assert table.stats.h2d_bytes == _table_bytes(table) + sum(
        _operand_bytes(table, b) for b in batches)


@pytest.mark.parametrize("kind,via", [("flat", "table"),
                                      ("sharded", "table"),
                                      ("sharded", "shard")])
def test_an_insert_after_a_lookup_is_found_by_the_next(kind, via):
    table = _table(kind)
    _fill(table, range(1, 80))
    new_key = 5000
    assert not np.asarray(table.lookup_batch(np.array([new_key]))[1]).any()
    record = np.random.default_rng(7).standard_normal(VDIM).astype(
        np.float32)
    if via == "table":
        table.insert(new_key, record)
    else:
        table.shards[table.shard_of(new_key)].insert(new_key, record)
    keys = np.array([3, new_key, 77])
    v, f = table.lookup_batch(keys)
    assert np.asarray(f).all()
    assert (_bits(np.asarray(v)[1]) == _bits(record)).all()
    assert table.stats.table_ships == 2
    table.lookup_batch(keys)
    assert table.stats.table_ships == 2


@pytest.mark.parametrize("kind,impls", [
    ("flat", ["pallas"]),
    ("sharded", ["pallas"])])
def test_every_impl_matches_ref_on_resident_tables(kind, impls):
    table = _table(kind)
    _fill(table, range(1, 150), seed=3)
    keys = np.concatenate([np.arange(1, 150, 2), np.arange(900, 930)])
    ref_v, ref_f = (np.asarray(a) for a in table.lookup_batch(keys,
                                                              impl="ref"))
    for impl in impls:
        v, f = (np.asarray(a) for a in table.lookup_batch(keys, impl=impl))
        np.testing.assert_array_equal(f, ref_f)
        np.testing.assert_array_equal(_bits(v), _bits(ref_v))
    assert ref_f[:75].all() and not ref_f[75:].any()
    assert table.stats.table_ships == 1


def test_sharded_host_tables_are_stacked_views():
    table = _table("sharded")
    _fill(table, range(1, 40))
    fp, val = table.tables()
    assert type(fp) is np.ndarray and type(val) is np.ndarray
    assert fp.shape == (3, 32, 8) and fp.dtype == np.int32
    assert val.shape == (3, 32, 8, VDIM) and val.dtype == np.float32
    assert table.tables()[1] is val         # no copy per call
    for i, shard in enumerate(table.shards):
        s_fp, s_val = shard.tables()
        assert s_fp.shape == (32, 8) and s_val.shape == (32, 8, VDIM)
        assert np.shares_memory(s_fp, fp) and np.shares_memory(s_val, val)
        np.testing.assert_array_equal(s_fp, fp[i])
    assert table.version == 39
    assert sum(int(s._loads.sum()) for s in table.shards) == 39
