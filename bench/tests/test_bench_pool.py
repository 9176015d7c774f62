"""The pool-layout cell: its check with the pool lookup broken underneath,
its least-bytes yardstick counted by hand, and the readers of its
per-layer metrics on synthetic traces and tiny tables."""

import functools
import time
import types

import numpy as np
import pytest

from bench import control, pool_roofline
from bench import run as harness
from bench import trace as tr
from bench.tests.test_bench_harness import (SEED, _alter_one_answer,
                                            _drop_half, _misplace, tiny)
from repro.kvs.race import PoolRaceTable, fp8, prep_keys

NSLOT, VDIM = 8, 256
SLOTS = NSLOT * 8                        # one bucket's 8-byte slots
KEY = 4                                  # a query key, or a block's key
RECORD = VDIM * 4                        # one record, read once
ANSWER = VDIM * 4 + 4                    # record and found flag, written
KERNEL = ('%pool_lookup.1 = (f32[4096,256]{1,0}, s32[4096,1]{1,0}) '
          'custom-call(s32[8192]{0} %reshape.1), '
          'custom_call_target="tpu_custom_call"')


def read(name, run):
    return harness.load_module(
        harness.BENCH / "metrics" / f"{name}.py").read(run)


@pytest.mark.parametrize("fault", [_alter_one_answer, _drop_half,
                                   _misplace])
def test_a_broken_pool_lookup_is_not_correct(fault, monkeypatch):
    """Break the pool lookup where its answers are produced (the kernel
    wrapper under ``PoolRaceTable.lookup_batch``): the run comes out
    incorrect."""
    from repro.kernels.race_lookup import ops

    real = ops.pool_lookup

    @functools.wraps(real)
    def broken(*args, **kwargs):
        values, found, blocks = real(*args, **kwargs)
        return (*fault(values, found), blocks)

    monkeypatch.setattr(ops, "pool_lookup", broken)
    out = harness.run_cell(tiny("pool-ycsbc-zipf"), SEED, 0.2, False,
                           t_start=time.perf_counter())
    assert out["correct"] is False
    assert out["failed"] == out["checks"]["mismatched_answers"]["value"] > 0


def test_the_bfloat16_control_is_not_correct_on_the_pool_cell():
    lines = list(control.run(tiny("pool-ycsbc-zipf"), [SEED, SEED + 1],
                             0.2))
    assert [line["correct"] for line in lines] == [False, False]
    for line in lines:
        assert line["mismatched_answers"] > 0.9 * line["answers_checked"]


def _fp8_by_python_ints(key):
    fp = ((key * 2246822519 + 1) & 0xFFFFFFFF) & 0x7FFFFFFF or 1
    return max((fp >> 23) & 0xFF, 1)


def test_the_yardstick_fingerprint_is_the_tables():
    keys = np.random.default_rng(1).integers(1, 2 ** 31 - 1, 3000)
    keys[:2] = [1, 2 ** 31 - 2]
    got = pool_roofline.fingerprints(keys)
    assert got.tolist() == [_fp8_by_python_ints(int(k)) for k in keys]
    fps, _ = prep_keys(keys, 7)
    assert got.tolist() == fp8(fps).tolist()


def test_least_bytes_of_a_tiny_table_counted_by_hand(monkeypatch):
    """Buckets 0-3 of 8 slots. Key 11 sits in bucket 1 (block 5) and a
    false match for it, block 6, in bucket 2; key 12 in bucket 3 (block
    7) beside a block whose fingerprint is another's (block 8). Asked:
    11 twice, 12, and 13, never loaded, whose buckets hold no match."""
    config = {"buckets": 4, "slots_per_bucket": NSLOT, "vdim": VDIM}
    rows = {11: [1, 2], 12: [3, 1], 13: [0, 2]}
    fp = np.zeros((4, NSLOT), np.int64)
    ptr = np.zeros((4, NSLOT), np.int64)
    fp[1, 0], ptr[1, 0] = 40, 5          # key 11's block
    fp[2, 3], ptr[2, 3] = 40, 6          # false match for key 11
    fp[3, 1], ptr[3, 1] = 41, 7          # key 12's block
    fp[3, 2], ptr[3, 2] = 99, 8          # another key's fingerprint
    fps = {11: 40, 12: 41, 13: 42}
    asked = np.array([11, 12, 11, 13])
    by_hand = (4 * SLOTS                 # buckets 0, 1, 2, 3
               + 3 * KEY                 # blocks 5, 6 and 7, once each
               + 2 * RECORD              # keys 11 and 12 found
               + 4 * (KEY + ANSWER))     # every query
    assert pool_roofline.least_bytes(
        np.array([rows[k] for k in asked]), 3, 2, 4, nslot=NSLOT,
        vdim=VDIM) == by_hand
    # the same count through the multi-get reader, with the placement and
    # fingerprints above standing in for the hashes
    monkeypatch.setattr(pool_roofline, "roofline", types.SimpleNamespace(
        candidate_rows=lambda keys, _: np.array([rows[k] for k in keys])))
    monkeypatch.setattr(pool_roofline, "fingerprints",
                        lambda keys: np.array([fps[k] for k in keys]))
    assert pool_roofline.multiget_least_bytes(
        asked, np.array([11, 12, 50]), (fp, ptr), config) == by_hand


def _loaded_table(n=600, nb=97):
    rng = np.random.default_rng(3)
    keys = rng.choice(2 ** 30, n, replace=False) + 1
    table = PoolRaceTable(n_buckets=nb, nslot=NSLOT, vdim=VDIM,
                          capacity=n)
    table.insert_many(keys, np.zeros((n, VDIM), np.float32))
    config = {"buckets": nb, "slots_per_bucket": NSLOT, "vdim": VDIM}
    return table, keys, config


def test_a_multiget_reads_no_fewer_bytes_than_the_table_fetches():
    """On a real table, the distinct blocks the yardstick counts are the
    distinct pointers of the program's fingerprint matches, and no block
    is counted twice however often it is asked for."""
    table, keys, config = _loaded_table()
    slots = pool_roofline.decode(table.tables()[0], config["buckets"],
                                 NSLOT)
    asked = np.concatenate([keys[:300], keys[:300], [2 ** 30 + 9]])
    got = pool_roofline.multiget_least_bytes(asked, np.sort(keys), slots,
                                             config)
    distinct = np.unique(asked)
    fps, bidx = prep_keys(distinct, config["buckets"])
    matched = {int(lo) for f, bs in zip(fp8(fps), bidx) for b in bs
               for hi, lo in table._slots[b]
               if hi and (int(hi) >> 24) & 0xFF == f}
    want = (np.unique(bidx).size * SLOTS + len(matched) * KEY
            + 300 * RECORD + len(asked) * (KEY + ANSWER))
    assert got == want
    assert len(matched) >= 300


def _run(table=None, trace=None, config=None, batches=(), loaded=None):
    return types.SimpleNamespace(
        table=table, trace=trace, batches=list(batches), loaded=loaded,
        device_kind="TPU v5 lite",
        cell=types.SimpleNamespace(config=config))


def test_blocks_per_key_from_the_tables_counts():
    table, keys, _ = _loaded_table()
    assert read("pool_blocks_per_key", _run(table)) is None   # no lookup
    table.lookup_batch(keys[:200])
    table.lookup_batch(np.arange(1, 50))
    got = read("pool_blocks_per_key", _run(table))
    assert got == int(table.stats.blocks) / 249
    assert got >= 200 / 249
    stats = types.SimpleNamespace(calls=2, keys=100, blocks=103)
    assert read("pool_blocks_per_key",
                _run(types.SimpleNamespace(stats=stats))) == 1.03


@pytest.mark.parametrize("table", [
    object(),                                             # no stats
    types.SimpleNamespace(stats=types.SimpleNamespace(    # no such count
        calls=5, keys=20, h2d_bytes=10))])
def test_no_block_count_to_read(table):
    assert read("pool_blocks_per_key", _run(table)) is None


def test_kernel_time_and_roofline_from_a_synthetic_trace():
    """Two multi-gets of 1 ms each; the pool kernel runs 100 us in each,
    beside an op of another name that is not counted."""
    table, keys, config = _loaded_table()
    batches = [keys[:64], keys[64:128]]
    host = [("multiget", 0, 1_000_000), ("multiget", 1_000_000, 2_000_000)]
    ops = [(KERNEL, 500_000, 600_000), (KERNEL, 1_500_000, 1_600_000),
           ("%copy.1 = f32[64] copy(%p)", 600_000, 700_000)]
    trace = tr.Trace(device_ops={0: ops}, host=host)
    run = _run(table, trace, config, batches, np.sort(keys))
    assert read("pool_lookup_kernel_ms", run) == pytest.approx(0.1)
    slots = pool_roofline.decode(table.tables()[0], config["buckets"],
                                 NSLOT)
    least = sum(pool_roofline.multiget_least_bytes(b, np.sort(keys), slots,
                                                   config)
                for b in batches) / 819e9
    assert read("pool_lookup_roofline", run) == pytest.approx(
        100 * least / 200e-6)
    # a trace without the pool kernel, or no trace, reads nothing
    other = tr.Trace(device_ops={0: ops[2:]}, host=host)
    for t in (other, None):
        for name in ("pool_lookup_kernel_ms", "pool_lookup_roofline"):
            assert read(name, _run(table, t, config, batches,
                                   np.sort(keys))) is None
