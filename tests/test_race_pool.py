"""RACE's published layout on the device (``PoolRaceTable``): an index of
8-byte slots over a pool of KV blocks that carry their keys, read by the
two-level pool kernel. Checked against a plain dictionary of the inserted
records and against the pure-jnp reference, on keys forced to collide in
bucket and fingerprint, and for the bulk insert's placement (the
contract all three device tables keep is ``test_device_tables.py``).
CPU, tiny tables (Pallas in interpret mode)."""

import contextlib

import numpy as np
import pytest

from repro import obs
from repro.kernels.race_lookup import ops
from repro.kernels.race_lookup.ref import pool_lookup_ref
from repro.kvs.race import PoolRaceTable, fp8, prep_keys

VDIM = 32
SEED = 2 ** 31 + 7


def _records(n, seed=0):
    rng = np.random.default_rng(seed)
    keys = rng.choice(2 ** 30, n, replace=False) + 1
    return keys, rng.standard_normal((n, VDIM)).astype(np.float32)


def _bits(a):
    return np.ascontiguousarray(a, np.float32).view(np.uint32)


def _expect(keys, vals, asked):
    """What a plain dictionary of the inserted records answers."""
    row = {int(k): i for i, k in enumerate(keys)}
    found = np.array([int(k) in row for k in asked])
    want = np.zeros((len(asked), VDIM), np.float32)
    for i, k in enumerate(asked):
        if int(k) in row:
            want[i] = vals[row[int(k)]]
    return want, found


def _lookup(table, asked, impl):
    v, f = table.lookup_batch(np.asarray(asked), impl=impl)
    return np.asarray(v), np.asarray(f)


def test_the_kernel_matches_the_reference_with_its_block_count():
    keys, vals = _records(500, seed=3)
    table = PoolRaceTable(n_buckets=101, nslot=8, vdim=VDIM, capacity=500)
    table.insert_many(keys, vals)
    asked = np.concatenate([keys[::3], np.arange(1, 40)])
    answers = {}
    for impl in ("pallas", "ref"):
        before = int(table.stats.blocks)
        answers[impl] = _lookup(table, asked, impl)
        answers[impl] += (int(table.stats.blocks) - before,)
    (pv, pf, pb), (rv, rf, rb) = answers["pallas"], answers["ref"]
    np.testing.assert_array_equal(pf, rf)
    np.testing.assert_array_equal(_bits(pv), _bits(rv))
    # every found key is one block; false fingerprint matches add more
    assert pb == rb >= pf.sum()


def _colliding(n_buckets, count):
    """``count`` keys with the same two candidate buckets and the same
    8-bit fingerprint, found by search."""
    keys = np.arange(1, 400_000)
    fps, bidx = prep_keys(keys, n_buckets)
    groups = {}
    for k, f, (b1, b2) in zip(keys.tolist(), fp8(fps).tolist(),
                              bidx.tolist()):
        g = groups.setdefault((b1, b2, f), [])
        g.append(k)
        if len(g) == count:
            return g
    raise AssertionError("no colliding keys")


def _fingerprint_only(table, asked):
    """RACE's read with the stored-key comparison left out: the record of
    the first slot whose fingerprint matches. The control for the test
    below, which this lookup must fail."""
    fps, bidx = prep_keys(asked, table.n_buckets)
    index, _, pool = table.tables()
    out = np.zeros((len(asked), table.vdim), np.float32)
    for i, (f, bs) in enumerate(zip(fp8(fps), bidx)):
        for b in bs:
            hits = [lo for hi, lo in table._slots[b]
                    if hi and (int(hi) >> 24) & 0xFF == f]
            if hits:
                out[i] = pool[hits[0], 0]
                break
    return out


@pytest.mark.parametrize("impl", ["pallas", "ref"])
def test_keys_sharing_bucket_and_fingerprint_get_their_own_records(impl):
    nb = 13
    keys = np.array(_colliding(nb, 6))
    vals = np.random.default_rng(SEED).standard_normal(
        (len(keys), VDIM)).astype(np.float32)
    table = PoolRaceTable(n_buckets=nb, nslot=8, vdim=VDIM, capacity=8)
    for k, v in zip(keys, vals):
        table.insert(int(k), v)
    # they fill their two buckets, and every slot holding one of them
    # matches every other's fingerprint
    _, ((b1, b2),) = prep_keys(keys[:1], nb)
    assert sum(table._loads[b] for b in {b1, b2}) == len(keys)
    asked = keys[::-1]
    v, f = _lookup(table, asked, impl)
    want, found = _expect(keys, vals, asked)
    assert f.all()
    np.testing.assert_array_equal(_bits(v), _bits(want))
    # the control: without the key comparison some key reads another's
    # record, so this test is tight
    assert (_bits(_fingerprint_only(table, asked)) != _bits(want)).any()
    # each lookup fetched the block of every fingerprint match
    assert int(table.stats.blocks) > len(keys)


def test_insert_many_places_keys_as_insert_does():
    keys, vals = _records(900, seed=4)
    one = PoolRaceTable(n_buckets=151, nslot=8, vdim=VDIM, capacity=1000)
    bulk = PoolRaceTable(n_buckets=151, nslot=8, vdim=VDIM, capacity=1000)
    for k, v in zip(keys, vals):
        one.insert(int(k), v)
    bulk.insert(int(keys[0]), vals[0])           # bulk after a single one
    bulk.insert_many(keys[1:500], vals[1:500])
    bulk.insert_many(keys[500:], vals[500:])
    for a, b in zip(one.tables(), bulk.tables()):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(one._loads, bulk._loads)
    assert one.size == bulk.size == 900
    assert one.version == 900 and bulk.version == 3


def test_an_overflowing_bulk_insert_writes_nothing():
    table = PoolRaceTable(n_buckets=2, nslot=8, vdim=VDIM, capacity=64)
    keys, vals = _records(40, seed=5)
    table.insert_many(keys[:10], vals[:10])
    before = [a.copy() for a in table.tables()]
    with pytest.raises(RuntimeError, match="overflow"):
        table.insert_many(keys[10:], vals[10:])
    for a, b in zip(before, table.tables()):
        np.testing.assert_array_equal(a, b)
    assert table.size == 10 and table.version == 1
    assert sum(table._loads) == 10


def test_slots_hold_fingerprint_length_and_pointer():
    table = PoolRaceTable(n_buckets=32, nslot=8, vdim=256, capacity=16)
    keys, _ = _records(5, seed=6)
    for k in keys:
        table.insert(int(k), np.ones(256, np.float32))
    index, bkeys, pool = table.tables()
    assert index.shape == (32 * 16 // 128, 1, 128)
    assert bkeys.shape == (1, 1, 128) and pool.shape == (16, 1, 256)
    fps, bidx = prep_keys(keys, 32)
    for row, (k, f, (b1, b2)) in enumerate(zip(keys, fp8(fps), bidx)):
        slots = np.concatenate([table._slots[b1], table._slots[b2]])
        (hi, lo), = [s for s in slots if s[1] == row and s[0]]
        hi = int(hi) & 0xFFFFFFFF
        assert hi >> 24 == f and 1 <= f <= 255
        assert (hi >> 16) & 0xFF == 17          # 1028 B in 64 B units
        assert hi & 0xFFFF == 0                 # pointer bits 47-32
        assert bkeys.reshape(-1)[lo] == k
    # an occupied slot never reads as empty; the rest are all zero
    occupied = table._slots[..., 0] != 0
    assert occupied.sum() == 5 and not table._slots[~occupied].any()


def test_a_lookup_after_an_insert_reships_and_finds_it():
    keys, vals = _records(60, seed=7)
    table = PoolRaceTable(n_buckets=64, nslot=8, vdim=VDIM, capacity=64)
    table.insert_many(keys[:50], vals[:50])
    _, f = _lookup(table, keys, "pallas")
    assert f[:50].all() and not f[50:].any()
    table.insert_many(keys[50:], vals[50:])
    v, f = _lookup(table, keys, "pallas")
    assert f.all()
    np.testing.assert_array_equal(_bits(v), _bits(vals))
    assert table.stats.table_ships == 2


def test_the_reference_reads_the_tables_as_shipped():
    """``pool_lookup_ref`` on the host arrays of ``tables()`` and the
    operands of ``prep`` gives the dictionary's answers."""
    keys, vals = _records(120, seed=8)
    table = PoolRaceTable(n_buckets=40, nslot=8, vdim=VDIM, capacity=128)
    table.insert_many(keys, vals)
    asked = np.concatenate([keys, [2 ** 30 + 5]])
    v, f, blocks = pool_lookup_ref(*table.tables(), *table.prep(asked),
                                   nslot=8)
    want, found = _expect(keys, vals, asked)
    np.testing.assert_array_equal(np.asarray(f), found.astype(np.int32))
    np.testing.assert_array_equal(_bits(np.asarray(v)), _bits(want))
    assert (np.asarray(blocks)[:-1] >= 1).all()


@pytest.fixture
def puts(monkeypatch):
    """The arguments of every query operands' ``race.to_device`` span."""
    seen, real = [], obs.span

    @contextlib.contextmanager
    def span(name, **args):
        with real(name, **args) as add:
            def more(**known):
                args.update(known)
                add(**known)
            yield more
        if name == "race.to_device" and "table_ships" not in args:
            seen.append(args)

    monkeypatch.setattr(obs, "span", span)
    return seen


@pytest.mark.parametrize("impl", ["pallas", "ref"])
@pytest.mark.parametrize("nq", [1, 3, 127, 128, 129, 300])
def test_one_packed_put_a_lookup(puts, nq, impl):
    """The query operands cross to the device as one packed array, one
    device buffer, whatever the batch's length against the 128 lanes;
    ``h2d_bytes`` counts that array, and the answers are the records."""
    keys, vals = _records(400, seed=9)
    table = PoolRaceTable(n_buckets=97, nslot=8, vdim=VDIM, capacity=400)
    table.insert_many(keys, vals)
    table.lookup_batch(keys[:1], impl=impl)          # ships the tables
    before = (table.stats.h2d_bytes, table.stats.h2d_buffers, len(puts))
    asked = np.concatenate([keys[::2], np.arange(1, 200)])[:nq]
    v, f = _lookup(table, asked, impl)
    queries = ops.pack_queries(*table.prep(asked))
    assert queries.shape == (4, -(-nq // 128) * 128)
    assert [(a["h2d_buffers"], a["h2d_bytes"]) for a in puts[before[2]:]] \
        == [(1, queries.nbytes)]
    assert table.stats.h2d_bytes - before[0] == queries.nbytes
    assert table.stats.h2d_buffers - before[1] == 1
    want, found = _expect(keys, vals, asked)
    np.testing.assert_array_equal(f, found.astype(np.int32))
    np.testing.assert_array_equal(_bits(v), _bits(want))


def test_the_packed_rows_are_the_operands():
    """Pool rows: keys, fingerprints, first and second bucket, in the
    query's lane; the mesh's fifth row is each node's chunk of ``inv``,
    zero-padded, and the lanes cover the wider of QCAP and the chunk."""
    qkeys = np.array([5, 6, 7], np.int32)
    fps = np.array([9, 10, 11], np.int32)
    bidx = np.array([[1, 2], [3, 4], [5, 6]], np.int32)
    got = ops.pack_queries(qkeys, fps, bidx)
    assert got.dtype == np.int32 and got.shape == (4, 128)
    np.testing.assert_array_equal(got[:, :3], [[5, 6, 7], [9, 10, 11],
                                               [1, 3, 5], [2, 4, 6]])
    assert not got[:, 3:].any()
    # two nodes of QCAP 8, seven keys: chunks of four
    inv = np.arange(7, dtype=np.int32) + 1
    grouped = ops.pack_queries(np.ones((2, 8), np.int32),
                               np.ones((2, 8), np.int32),
                               np.ones((2, 8, 2), np.int32), inv)
    assert grouped.shape == (2, 5, 128)
    np.testing.assert_array_equal(grouped[:, 4, :4], [[1, 2, 3, 4],
                                                      [5, 6, 7, 0]])
    assert not grouped[:, 4, 4:].any() and grouped[:, :4, :8].all()
    assert not grouped[:, :4, 8:].any()
    wide = ops.pack_queries(np.ones((2, 8), np.int32),
                            np.ones((2, 8), np.int32),
                            np.ones((2, 8, 2), np.int32),
                            np.arange(300, dtype=np.int32))
    assert wide.shape == (2, 5, 256)
    np.testing.assert_array_equal(wide[:, 4, :150].reshape(-1),
                                  np.arange(300))
