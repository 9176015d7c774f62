"""The cell of RACE's pool over four memory nodes (``pool4-ycsbc-zipf``):
a tiny run on four virtual chips is correct, and incorrect with the pool
lookup broken underneath on every node or with the bfloat16 control in
the program's place; the least bytes of its roofline counted by hand on
a tiny two-node table; the cross-chip collectives' time read from a
synthetic trace of two chips."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from bench import pool_roofline
from bench import run as harness
from bench import trace as tr
from repro.kvs.race import MeshPoolRaceTable, shard_of_keys

ROOT = harness.ROOT
CELL = "pool4-ycsbc-zipf"
FAULTS = ["_alter_one_answer", "_drop_half", "_misplace"]
NSLOT, VDIM = 8, 256
SLOTS = NSLOT * 8                        # one bucket's 8-byte slots
KEY = 4                                  # a query key, or a block's key
RECORD = VDIM * 4                        # one record, read once
ANSWER = VDIM * 4 + 4                    # record and found flag, written
KERNEL = ('%pool_lookup.1 = (f32[1280,256]{1,0}, s32[1280,1]{1,0}) '
          'custom-call(s32[2560]{0} %reshape.1), '
          'custom_call_target="tpu_custom_call"')

CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import functools, json, sys, time
sys.path[:0] = [os.path.join(%(root)r, "src"), %(root)r]
import numpy as np
import jax
from bench import control
from bench import run as harness
from bench.tests import test_bench_harness as cases
from repro.kernels.race_lookup import ops

real = ops.pool_lookup


def breaking(fault):
    # the kernel's answers on each node are traced values: the fault runs
    # on the host as they are produced
    @functools.wraps(real)
    def broken(*args, **kwargs):
        values, found, blocks = real(*args, **kwargs)
        values, found = jax.pure_callback(
            lambda v, f: tuple(np.asarray(a) for a in fault(v, f)),
            (jax.ShapeDtypeStruct(values.shape, values.dtype),
             jax.ShapeDtypeStruct(found.shape, found.dtype)), values, found)
        return values, found, blocks
    return broken


def run(trace, seconds):
    out = harness.run_cell(cases.tiny(%(cell)r), cases.SEED, seconds, trace,
                           t_start=time.perf_counter())
    return {k: out[k] for k in ("correct", "failed", "attempted",
                                "answers_checked", "metrics", "device")}


result = {"devices": len(jax.devices()), "traced": run(True, 0.3),
          "untraced": run(False, 0.3)}
for name in %(faults)r:
    ops.pool_lookup = breaking(getattr(cases, name))
    jax.clear_caches()               # the lookup program traces it anew
    result[name] = run(False, 0.2)
ops.pool_lookup = real
result["control"] = list(control.run(cases.tiny(%(cell)r),
                                     [cases.SEED, cases.SEED + 1], 0.2))
print("RESULT " + json.dumps(result))
"""


@pytest.fixture(scope="module")
def four_chips():
    """The cell's runs on four virtual CPU devices, one memory node each,
    in a child process (this one may hold JAX's backend with one)."""
    code = CODE % {"root": str(ROOT), "cell": CELL, "faults": FAULTS}
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    lines = [x for x in p.stdout.splitlines() if x.startswith("RESULT ")]
    assert lines, p.stdout + p.stderr
    return json.loads(lines[-1][len("RESULT "):])


@pytest.mark.parametrize("trace", ["traced", "untraced"])
def test_a_tiny_run_on_four_chips_is_correct(four_chips, trace):
    out = four_chips[trace]
    assert four_chips["devices"] == 4 and out["device"]["count"] == 4
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["answers_checked"] == out["attempted"]
    if trace == "traced":
        assert out["metrics"]["compiles_in_window"]["value"] == 0
        assert {"prep_ms", "route_ms", "to_device_ms", "shard_pad_pct",
                "table_ship_pct", "pool_blocks_per_key"} <= set(
                    out["metrics"])
    else:
        assert set(out["metrics"]) == {"lookups_per_s", "setup_s"}


@pytest.mark.parametrize("fault", FAULTS)
def test_a_broken_pool_lookup_on_every_node_is_not_correct(four_chips,
                                                           fault):
    out = four_chips[fault]
    assert out["correct"] is False and out["failed"] > 0


def test_the_bfloat16_control_is_not_correct(four_chips):
    lines = four_chips["control"]
    assert [line["correct"] for line in lines] == [False, False]
    for line in lines:
        assert line["mismatched_answers"] > 0.9 * line["answers_checked"]


def _reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py")


def test_the_yardstick_routes_keys_as_the_table():
    keys = np.random.default_rng(4).integers(1, 2 ** 31 - 1, 5000)
    for n in (1, 2, 4, 7):
        assert _reader("mesh_pool_lookup_roofline").node_of(
            keys, n).tolist() == shard_of_keys(keys, n).tolist()


def test_least_bytes_of_a_tiny_two_node_pool_counted_by_hand(monkeypatch):
    """Three nodes of buckets 0-3. Node 0 holds key 11 in bucket 1 (block
    5), with a false match for it in bucket 2 (block 6); node 1 holds key
    12 in bucket 3 (block 7) and key 14 in bucket 0 (block 2); node 2
    holds no key. Asked: 11 twice, 13 (never loaded, node 0), 12, 14
    twice and 15 (node 2)."""
    reader = _reader("mesh_pool_lookup_roofline")
    config = {"buckets": 4, "slots_per_bucket": NSLOT, "vdim": VDIM}
    rows = {11: [1, 2], 13: [0, 2], 12: [3, 1], 14: [0, 3], 15: [2, 3]}
    fps = {11: 40, 12: 41, 13: 42, 14: 43, 15: 44}
    owner = {11: 0, 13: 0, 12: 1, 14: 1, 15: 2}
    slots = []
    for placed in ({(1, 0): (40, 5), (2, 3): (40, 6)},
                   {(3, 1): (41, 7), (0, 0): (43, 2)}, {}):
        fp = np.zeros((4, NSLOT), np.int64)
        ptr = np.zeros((4, NSLOT), np.int64)
        for (b, s), (f, p) in placed.items():
            fp[b, s], ptr[b, s] = f, p
        slots.append((fp, ptr))
    monkeypatch.setattr(reader, "node_of", lambda keys, n: np.array(
        [owner[int(k)] for k in keys], np.uint64))
    monkeypatch.setattr(pool_roofline, "roofline", types.SimpleNamespace(
        candidate_rows=lambda keys, _: np.array([rows[k] for k in keys])))
    monkeypatch.setattr(pool_roofline, "fingerprints",
                        lambda keys: np.array([fps[k] for k in keys]))
    by_hand = (3 * SLOTS + 2 * KEY + 1 * RECORD + 3 * (KEY + ANSWER)  # node 0
               + 3 * SLOTS + 2 * KEY + 2 * RECORD + 3 * (KEY + ANSWER)
               + 2 * SLOTS + 1 * (KEY + ANSWER))                    # node 2
    asked = np.array([11, 12, 11, 13, 14, 14, 15])
    assert reader.least_bytes([asked], np.array([11, 12, 14]), slots,
                              config) == by_hand
    # a batch counts once per multi-get, batch after batch
    assert reader.least_bytes([asked, asked], np.array([11, 12, 14]),
                              slots, config) == 2 * by_hand


def _two_node_table(n=400, nb=61):
    rng = np.random.default_rng(6)
    keys = rng.choice(2 ** 30, n, replace=False) + 1
    table = MeshPoolRaceTable(n_nodes=2, n_buckets=nb, nslot=NSLOT,
                              vdim=VDIM, capacity=n)
    table.insert_many(keys, np.zeros((n, VDIM), np.float32))
    return table, keys, {"buckets": nb, "slots_per_bucket": NSLOT,
                         "vdim": VDIM}


def test_least_bytes_are_each_nodes_own_on_a_real_table():
    """On a loaded two-node table: the sum, over the nodes, of the pool
    yardstick on each node's own keys and index."""
    table, keys, config = _two_node_table()
    reader = _reader("mesh_pool_lookup_roofline")
    asked = np.concatenate([keys[:150], keys[:50], [2 ** 30 + 7]])
    slots = [pool_roofline.decode(node.tables()[0], config["buckets"],
                                  NSLOT) for node in table.nodes]
    node = shard_of_keys(asked, 2)
    loaded = shard_of_keys(keys, 2)
    want = sum(pool_roofline.multiget_least_bytes(
        asked[node == i], np.sort(keys[loaded == i]), slots[i], config)
        for i in range(2))
    assert reader.least_bytes([asked], np.sort(keys), slots, config) == want


def _run(table=None, trace=None, config=None, batches=(), loaded=None):
    return types.SimpleNamespace(
        table=table, trace=trace, batches=list(batches), loaded=loaded,
        device_kind="TPU v5 lite",
        cell=types.SimpleNamespace(config=config))


MULTIGETS = [("multiget", 0, 1_000_000), ("multiget", 1_000_000, 2_000_000)]


def test_the_roofline_from_a_synthetic_trace_of_two_chips():
    """Two multi-gets; the pool kernel runs 100 us a multi-get on chip 0
    and 300 us on chip 1. The least time spreads over both chips' HBM and
    is divided by the mean kernel time per chip."""
    table, keys, config = _two_node_table()
    batches = [keys[:64], keys[64:128]]
    ops = {0: [(KERNEL, 100_000, 200_000), (KERNEL, 1_100_000, 1_200_000)],
           1: [(KERNEL, 100_000, 400_000), (KERNEL, 1_100_000, 1_400_000)]}
    run = _run(table, tr.Trace(device_ops=ops, host=MULTIGETS), config,
               batches, np.sort(keys))
    reader = _reader("mesh_pool_lookup_roofline")
    slots = [pool_roofline.decode(node.tables()[0], config["buckets"],
                                  NSLOT) for node in table.nodes]
    least = reader.least_bytes(batches, np.sort(keys), slots, config) / 819e9
    mean_kernel = (200e-6 + 600e-6) / 2
    assert reader.read(run) == pytest.approx(100 * least / 2 / mean_kernel)
    assert _reader("pool_lookup_kernel_ms").read(run) == pytest.approx(0.2)
    # no trace, no pool kernel in it, or a table without nodes
    other = tr.Trace(device_ops={0: [("%copy.1 = f32[8] copy(%p)", 0, 9)]},
                     host=MULTIGETS)
    for r in (_run(table, None, config, batches, np.sort(keys)),
              _run(table, other, config, batches, np.sort(keys)),
              _run(object(), run.trace, config, batches, np.sort(keys))):
        assert reader.read(r) is None


def test_the_collectives_time_from_a_synthetic_trace_of_two_chips():
    """Chip 0 runs a synchronous all-gather (40 us) and a scalar
    all-reduce named by its psum (5 us) in each multi-get; chip 1 the
    asynchronous all-gather's start (10 us) and done (20 us) halves and a
    collective-permute (15 us). Neither the kernel nor the fusion that
    reads the all-gather's result counts."""
    gather = ("%all-gather.1 = f32[5120,256]{1,0:T(8,128)} all-gather("
              "f32[1280,256]{1,0} %x), channel_id=2, dimensions={0}")
    psum = "%psum.7 = s32[]{:T(128)} all-reduce(s32[] %r), channel_id=1"
    start = ("%all-gather-start = (f32[1280,256]{1,0}, f32[5120,256]{1,0}) "
             "all-gather-start(f32[1280,256]{1,0} %x)")
    done = ("%all-gather-done = f32[5120,256]{1,0} all-gather-done("
            "(f32[1280,256]{1,0}, f32[5120,256]{1,0}) %all-gather-start)")
    permute = ("%collective-permute.2 = f32[8]{0} collective-permute("
               "f32[8]{0} %y), source_target_pairs={{0,1},{1,0}}")
    fusion = ("%fusion = f32[4096,256]{1,0} fusion(f32[5120,256]{1,0} "
              "%all-gather.1, s32[4096]{0} %f), kind=kCustom")
    ops = {0: [], 1: []}
    for base in (0, 1_000_000):
        ops[0] += [(gather, base + 10_000, base + 50_000),
                   (psum, base + 50_000, base + 55_000),
                   (KERNEL, base, base + 10_000),
                   (fusion, base + 55_000, base + 95_000)]
        ops[1] += [(start, base + 10_000, base + 20_000),
                   (done, base + 40_000, base + 60_000),
                   (permute, base + 60_000, base + 75_000)]
    reader = _reader("node_gather_ms")
    run = _run(trace=tr.Trace(device_ops=ops, host=MULTIGETS))
    per_chip = (2 * (40 + 5) + 2 * (10 + 20 + 15)) / 2      # us
    assert reader.read(run) == pytest.approx(per_chip / 2 / 1000)
    assert [reader.is_collective(h) for h in
            (gather, psum, start, done, permute, fusion, KERNEL)] == \
        [True] * 5 + [False] * 2
    # one chip, or no trace: nothing to read
    one = {0: [(KERNEL, 0, 10_000)]}
    assert reader.read(_run(trace=tr.Trace(device_ops=one,
                                           host=MULTIGETS))) is None
    assert reader.read(_run()) is None
