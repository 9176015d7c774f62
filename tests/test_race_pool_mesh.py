"""RACE's pool over several memory nodes (``MeshPoolRaceTable``): four
nodes laid out over four, two and one virtual CPU devices, each node
bit-equal to a ``PoolRaceTable`` of the keys routed to it, every answer
equal to the benchmark's plain reference (``bench/references/kv_index``)
through the kernel and through the pure-jnp oracle, and the table's
counters. One child process with four host devices runs every check, so
JAX starts once: the test process may already hold its backend with one
device. CPU, tiny tables (Pallas in interpret mode)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

CASES = ["zipf_duplicates", "missing", "empty", "one_node_idle",
         "one_node_only", "ragged", "fewer_than_nodes"]
LAYOUTS = [4, 2, 1]            # devices for the four nodes
IMPLS = ["pallas", "ref"]

CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import contextlib, importlib.util, json, sys
sys.path[:0] = [os.path.join(%(root)r, "src"), %(root)r]
import numpy as np
import jax
from repro import obs
from repro.kernels.race_lookup import ops
from repro.kernels.race_lookup.pool import QBLOCK
from repro.kernels.race_lookup.race_lookup import _group_slots
from repro.kvs.race import MeshPoolRaceTable, PoolRaceTable, shard_of_keys

spec = importlib.util.spec_from_file_location(
    "kv_index", os.path.join(%(root)r, "bench", "references", "kv_index.py"))
kv_index = importlib.util.module_from_spec(spec)
spec.loader.exec_module(kv_index)

N, NB, VDIM, RECORDS = 4, 61, 32, 700
rng = np.random.default_rng(2 ** 31 + 5)
keys = rng.choice(2 ** 30, RECORDS, replace=False) + 1
vals = rng.standard_normal((RECORDS, VDIM)).astype(np.float32)
node = shard_of_keys(keys, N)
capacity = int(np.bincount(node, minlength=N).max()) + 8   # room to insert
reference = kv_index.build(keys, vals)

absent = np.arange(2 ** 30 + 1, 2 ** 30 + 200)
zipf = np.minimum(rng.zipf(1.3, 300), RECORDS) - 1
batches = {
    "zipf_duplicates": keys[zipf],
    "missing": np.concatenate([keys[:40], absent[:60]]),
    "empty": np.zeros(0, np.int64),
    "one_node_idle": keys[node != 2][:90],
    "one_node_only": np.concatenate([keys[node == 1][:70],
                                     absent[shard_of_keys(absent, N) == 1]]),
    "ragged": np.concatenate([keys[-250:], absent[:51]]),
    "fewer_than_nodes": np.array([keys[5], absent[3], keys[9]]),
}
out = {"answers": {}, "stats": {}}
puts = []            # each query operands' race.to_device: its arguments
active = []          # and the device_puts made inside it, while it is open
real_span, real_put = obs.span, jax.device_put


@contextlib.contextmanager
def span(name, **args):
    if name != "race.to_device" or "table_ships" in args:
        with real_span(name, **args) as add:
            yield add
        return
    seen = {"device_puts": []}
    active.append(seen)
    with real_span(name, **args) as add:
        def more(**known):
            seen.update(known)
            add(**known)
        yield more
    active.pop()
    puts.append(seen)


def device_put(x, *a, **k):
    out = real_put(x, *a, **k)
    if active:
        active[-1]["device_puts"].append(
            [list(np.shape(x)), str(out.sharding.spec)])
    return out


obs.span = span
jax.device_put = device_put


def packed(table, asked):
    # the packed queries of asked, as lookup_batch groups them
    qkeys, qfps, bidx, node = table.prep(asked)
    qfps, bidx, inv, _ = _group_slots(qfps, bidx, node, N, QBLOCK)
    grouped = np.zeros(qfps.size, np.int32)
    grouped[inv] = qkeys
    return ops.pack_queries(grouped.reshape(qfps.shape), qfps, bidx, inv)


def bits(a):
    return np.ascontiguousarray(a, np.float32).view(np.uint32)


for d in %(layouts)r:
    table = MeshPoolRaceTable(n_nodes=N, n_buckets=NB, nslot=8, vdim=VDIM,
                              capacity=capacity, devices=jax.devices()[:d])
    table.insert_many(keys, vals)
    if d == 4:
        same = []
        for i, got in enumerate(table.nodes):
            alone = PoolRaceTable(n_buckets=NB, nslot=8, vdim=VDIM,
                                  capacity=capacity)
            alone.insert_many(keys[node == i], vals[node == i])
            same.append(all(np.array_equal(a, b) for a, b in
                            zip(got.tables(), alone.tables())))
        out["nodes_equal"] = same
    for case, asked in batches.items():
        for impl in %(impls)r:
            before = (table.stats.slots, table.stats.padded_slots,
                      int(table.stats.blocks), table.stats.h2d_bytes,
                      table.stats.h2d_buffers, table.stats.table_ships,
                      len(puts))
            v, f = table.lookup_batch(asked, impl=impl)
            ships = table.stats.table_ships - before[5]
            mine = puts[before[6]:]
            v, f = np.asarray(v), np.asarray(f)
            want_v, want_f = reference.get_many(asked)
            bad = (f != 0) != want_f
            if len(asked):
                bad |= np.any(bits(v) != bits(want_v), axis=1)
            out["answers"][f"{case}-{impl}-{d}"] = {
                "shape": [list(v.shape), list(f.shape)],
                "dtype": [str(v.dtype), str(f.dtype)],
                "mismatched": int(bad.sum()),
                "found": int(f.sum()),
                "slots": table.stats.slots - before[0],
                "padded": table.stats.padded_slots - before[1],
                "blocks": int(table.stats.blocks) - before[2],
                "puts": [[a["h2d_buffers"], a["h2d_bytes"]] for a in mine],
                "device_puts": [a["device_puts"] for a in mine],
                "packed_shape": list(packed(table, asked).shape)
                if len(asked) else [],
                "packed_bytes": packed(table, asked).nbytes if len(asked)
                else 0,
                "h2d_buffers": table.stats.h2d_buffers - before[4]
                - ships * 3 * N,
                "h2d_bytes": table.stats.h2d_bytes - before[3]
                - ships * sum(a.nbytes for n in table.tables() for a in n),
                "device": isinstance(
                    table.lookup_batch(asked[:1], impl=impl)[0], jax.Array)}
    out["stats"][str(d)] = {"calls": table.stats.calls,
                            "keys": table.stats.keys,
                            "table_ships": table.stats.table_ships}
    table.insert(2 ** 30 + 500, np.ones(VDIM, np.float32))
    table.lookup_batch(np.array([2 ** 30 + 500]))
    out["stats"][str(d)]["ships_after_insert"] = table.stats.table_ships
# the benchmark's warm-up compiles every program a window of the same
# batch length runs: a lookup of a sampled batch adds no compiled program
from bench.systems import race_pool_mesh
table = MeshPoolRaceTable(n_nodes=N, n_buckets=NB, nslot=8, vdim=VDIM,
                          capacity=capacity, devices=jax.devices())
table.insert_many(keys, vals)
sample = [keys[rng.integers(0, RECORDS, 64)] for _ in range(8)]
shapes = race_pool_mesh.warm_up(table, sample)
cached = ops.pool_mesh_lookup._cache_size()
for asked in sample:
    table.lookup_batch(asked)
out["warm"] = {"shapes": shapes,
               "added": ops.pool_mesh_lookup._cache_size() - cached}
out["batch_nodes"] = {case: np.bincount(shard_of_keys(b, N),
                                        minlength=N).tolist()
                      for case, b in batches.items()}
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def result():
    # virtual host devices in a child: this process may have started JAX
    # with one device already
    code = CODE % {"root": ROOT, "layouts": LAYOUTS, "impls": IMPLS}
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    lines = [x for x in p.stdout.splitlines() if x.startswith("RESULT ")]
    assert lines, p.stdout + p.stderr
    return json.loads(lines[-1][len("RESULT "):])


def test_each_node_is_the_pool_table_of_its_keys(result):
    assert result["nodes_equal"] == [True] * 4


def test_the_batches_cover_what_they_claim(result):
    nodes = result["batch_nodes"]
    assert sum(nodes["empty"]) == 0
    assert nodes["one_node_idle"][2] == 0 and min(
        n for i, n in enumerate(nodes["one_node_idle"]) if i != 2) > 0
    assert [n > 0 for n in nodes["one_node_only"]] == [False, True, False,
                                                       False]


@pytest.mark.parametrize("devices", LAYOUTS)
@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("case", CASES)
def test_answers_are_the_references(result, case, impl, devices):
    got = result["answers"][f"{case}-{impl}-{devices}"]
    assert got["mismatched"] == 0
    n = {"zipf_duplicates": 300, "missing": 100, "empty": 0,
         "one_node_idle": 90, "ragged": 301, "fewer_than_nodes": 3}.get(case)
    if n is not None:
        assert got["shape"] == [[n, 32], [n]]
    assert got["dtype"] == ["float32", "int32"]
    assert got["device"]
    if case == "missing":
        assert got["found"] == 40
    if case == "zipf_duplicates":
        assert got["found"] == 300
    if case == "ragged":
        assert got["found"] == 250
    if case == "fewer_than_nodes":
        assert got["found"] == 2


@pytest.mark.parametrize("devices", LAYOUTS)
@pytest.mark.parametrize("case", CASES)
def test_one_packed_put_a_lookup_one_buffer_a_device(result, case, devices):
    """The queries cross to the devices in one ``race.to_device``: one
    array, sharded by node, so one device buffer per device and nothing
    replicated; its bytes are the packed array's, and the table's
    counters agree with the span's arguments. An empty batch puts
    nothing."""
    for impl in IMPLS:
        got = result["answers"][f"{case}-{impl}-{devices}"]
        if case == "empty":
            assert got["puts"] == [] and got["packed_bytes"] == 0
        else:
            assert got["puts"] == [[devices, got["packed_bytes"]]]
            assert got["device_puts"] == [[[got["packed_shape"],
                                            "PartitionSpec('node',)"]]]
            n, rows, lanes = got["packed_shape"]
            assert (n, rows) == (4, 5) and lanes % 128 == 0
        assert got["h2d_buffers"] == len(got["puts"]) * devices
        assert got["h2d_bytes"] == got["packed_bytes"]


def test_the_warm_up_compiles_what_a_lookup_runs(result):
    warm = result["warm"]
    assert warm["shapes"] and warm["added"] == 0


@pytest.mark.parametrize("devices", LAYOUTS)
def test_grouping_and_block_counts(result, devices):
    """Slots are 4 nodes x the busiest node's count rounded up to the
    pool kernel's tile (8 below 64 keys); every found key fetches its
    block, and the kernel and the oracle count alike."""
    nodes = result["batch_nodes"]
    for case in CASES:
        busiest = max(nodes[case])
        qcap = -(-busiest // 8) * 8 if busiest <= 64 else \
            -(-busiest // 64) * 64
        for impl in IMPLS:
            got = result["answers"][f"{case}-{impl}-{devices}"]
            assert got["slots"] == 4 * qcap
            assert got["padded"] == 4 * qcap - sum(nodes[case])
            assert got["blocks"] >= got["found"]
        assert result["answers"][f"{case}-pallas-{devices}"]["blocks"] == \
            result["answers"][f"{case}-ref-{devices}"]["blocks"]


@pytest.mark.parametrize("devices", LAYOUTS)
def test_the_table_ships_once_per_version(result, devices):
    stats = result["stats"][str(devices)]
    # each case's lookup per impl, and its one-key repeat
    assert stats["calls"] == 2 * len(CASES) * len(IMPLS)
    assert stats["table_ships"] == 1
    assert stats["ships_after_insert"] == 2
