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
         "one_node_only"]
LAYOUTS = [4, 2, 1]            # devices for the four nodes
IMPLS = ["pallas", "ref"]

CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import importlib.util, json, sys
sys.path.insert(0, os.path.join(%(root)r, "src"))
import numpy as np
import jax
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
}
out = {"answers": {}, "stats": {}}


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
                      int(table.stats.blocks))
            v, f = table.lookup_batch(asked, impl=impl)
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
                "device": isinstance(
                    table.lookup_batch(asked[:1], impl=impl)[0], jax.Array)}
    out["stats"][str(d)] = {"calls": table.stats.calls,
                            "keys": table.stats.keys,
                            "table_ships": table.stats.table_ships}
    table.insert(2 ** 30 + 500, np.ones(VDIM, np.float32))
    table.lookup_batch(np.array([2 ** 30 + 500]))
    out["stats"][str(d)]["ships_after_insert"] = table.stats.table_ships
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
         "one_node_idle": 90}.get(case)
    if n is not None:
        assert got["shape"] == [[n, 32], [n]]
    assert got["dtype"] == ["float32", "int32"]
    assert got["device"]
    if case == "missing":
        assert got["found"] == 40
    if case == "zipf_duplicates":
        assert got["found"] == 300


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
