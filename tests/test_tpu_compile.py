"""The main path's Pallas kernels compile for a TPU v5e (Mosaic), at the
benchmark's sizes (``bench/configs``, ``bench/traffic``): the flat
table's lookup kernel at ``race-flat-1m-1kb``'s 262,139 buckets, the
sharded kernel at ``race-sharded-1m-1kb``'s shard geometry, both at
vdim 256 and 4096-key multi-gets, with the gather that orders the
sharded answers, the pool-layout lookup at one memory
node's 8M records with its index and pool in HBM, the SPMD program of
the pool over four memory nodes at 4 x 8M records on a described
four-chip host, and the serverless stage gather at chunk 128.

Nothing runs: the chip is described, not attached, so these compile
from shapes alone and check that Mosaic accepts each kernel."""

import functools
import json
import os
from pathlib import Path

import pytest

import jax
import jax.numpy as jnp

from repro.kernels.race_lookup import pool as pool_kernel
from repro.kernels.race_lookup.ops import (NODE_AXIS, pack_queries,
                                           pool_lookup, pool_mesh_lookup)
from repro.kernels.race_lookup.pool import LANES, pool_lookup_pallas
from repro.kernels.race_lookup.race_lookup import (in_key_order,
                                                   race_lookup_pallas,
                                                   sharded_lookup_call)
from repro.kernels.serverless_stage.stage import CHUNK, chunk_gather_pallas

BENCH = Path(__file__).resolve().parent.parent / "bench"
FLAT, SHARDED, POOL, MESH = (
    json.loads((BENCH / f"configs/race-{name}-1kb.json").read_text())
    for name in ("flat-1m", "sharded-1m", "pool-8m", "pool-4x8m"))
NQ = json.loads(
    (BENCH / "traffic/ycsbc-zipf.json").read_text())["multiget_keys"]
NSLOT, VDIM = FLAT["slots_per_bucket"], FLAT["vdim"]
POOL_RECORDS, POOL_BUCKETS = POOL["recordcount"], POOL["buckets"]
SLAB_CHUNKS = 16 * (1024 // 4 // CHUNK)      # one chain slab of 16 x 1 KB


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape_on_chip(topo):
    """ShapeDtypeStruct factory placed on the described chip; the
    persistent compilation cache is off meanwhile (an entry written for
    a described chip cannot be read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype=jnp.int32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _scalar(s):
    nb = FLAT["buckets"]
    return jax.jit(functools.partial(race_lookup_pallas, interpret=False)
                   ).lower(s((nb, NSLOT)), s((nb, NSLOT, VDIM), jnp.float32),
                           s((NQ,)), s((NQ, 2)))


def _sharded(s):
    ns, nb, qcap = SHARDED["n_shards"], SHARDED["buckets_per_shard"], 64
    return sharded_lookup_call.lower(
        s((ns, nb, NSLOT)), s((ns, nb, NSLOT, VDIM), jnp.float32),
        s((ns, qcap)), s((ns, qcap, 2)), qblock=64, interpret=False)


def _pool(s):
    index_rows = -(-POOL_BUCKETS * 2 * NSLOT // LANES)
    return jax.jit(functools.partial(pool_lookup_pallas, nslot=NSLOT,
                                     interpret=False)
                   ).lower(s((index_rows, 1, LANES)),
                           s((-(-POOL_RECORDS // LANES), 1, LANES)),
                           s((POOL_RECORDS, 1, VDIM), jnp.float32),
                           s((NQ,)), s((NQ,)), s((NQ, 2)))


def _stage(s):
    return jax.jit(functools.partial(chunk_gather_pallas, chunk=CHUNK,
                                     interpret=False)
                   ).lower(s((SLAB_CHUNKS, CHUNK)), s((SLAB_CHUNKS,)),
                           s((SLAB_CHUNKS,)))


@pytest.mark.parametrize("lower", [_scalar, _sharded, _pool, _stage],
                         ids=["race_scalar", "race_sharded", "race_pool",
                              "stage_gather"])
def test_kernel_compiles_for_v5e(shape_on_chip, lower):
    compiled = lower(shape_on_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_the_pool_kernel_leaves_index_and_pool_where_they_are(
        shape_on_chip):
    """The resident index, keys and pool reach the kernel as they are:
    no copy or relayout of them in the compiled call, only of the query
    operands."""
    text = _pool(shape_on_chip).compile().as_text()
    for line in text.splitlines():
        if " copy(" in line or "copy-start(" in line:
            assert not any(f"%{p}" in line.split("copy", 1)[1]
                           for p in ("index", "keys", "pool")), line


def test_the_pool_table_unpacks_its_packed_queries_on_the_chip(
        shape_on_chip, monkeypatch):
    """``PoolRaceTable``'s own path at one node's 8M records: the jitted
    ``pool_lookup`` takes the 4096 queries as one packed (4, 4096) array,
    unpacks it on the chip and runs the kernel (``%pool_lookup``), with
    the resident index, keys and pool reaching it as they are."""
    monkeypatch.setattr(pool_kernel, "interpret_mode", lambda: False)
    s = shape_on_chip
    text = pool_lookup.lower(
        s((-(-POOL_BUCKETS * 2 * NSLOT // LANES), 1, LANES)),
        s((-(-POOL_RECORDS // LANES), 1, LANES)),
        s((POOL_RECORDS, 1, VDIM), jnp.float32), s((4, NQ)), nq=NQ,
        nslot=NSLOT).compile().as_text()
    assert any(line.lstrip().startswith("%pool_lookup")
               and 'custom_call_target="tpu_custom_call"' in line
               for line in text.splitlines())
    for line in text.splitlines():
        if " copy(" in line or "copy-start(" in line:
            assert not any(f"%{p}" in line.split("copy", 1)[1]
                           for p in ("index", "keys", "pool")), line


def test_the_sharded_answers_are_ordered_by_a_plain_gather(shape_on_chip):
    """The padded answers of a Zipfian multi-get (QCAP 256) go into the
    keys' order on the chip by an XLA gather: no Pallas kernel, and a
    name that the kernel's trace metrics do not match."""
    ns, qcap = SHARDED["n_shards"], 256
    s = shape_on_chip
    text = in_key_order.lower(s((ns, qcap, VDIM), jnp.float32),
                              s((ns, qcap)), s((NQ,))).compile().as_text()
    assert "HloModule jit_in_key_order" in text and "gather(" in text
    assert "tpu_custom_call" not in text
    assert not any(f"%{k}" in text
                   for k in ("race_lookup", "sharded_lookup_call"))


def test_the_pool_over_four_nodes_compiles_one_node_a_chip(topo,
                                                           shape_on_chip,
                                                           monkeypatch):
    """The SPMD lookup of ``race-pool-4x8m-1kb`` on a described four-chip
    v5e: each chip holds one node's 8M-record index and pool and runs the
    pool kernel (named ``pool_lookup`` in the HLO, which the trace
    metrics match) on its node's queries, at the busiest node's padded
    size for a Zipfian 4096-key multi-get, unpacked on the chip from its
    block of the one packed query array; a collective brings the answers
    to every chip. One node's tables and the program's own buffers fit a
    chip's 16 GB, with no copy of a pool."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import numpy as np

    # the kernel compiles for the described chip, not in interpret mode
    monkeypatch.setattr(pool_kernel, "interpret_mode", lambda: False)
    n, nb, qcap = MESH["nodes"], MESH["buckets"], 1280
    cap = -(-MESH["recordcount"] // n) + 4096      # the busiest node's
    mesh = Mesh(np.array(topo.devices[:n]), (NODE_AXIS,))
    by_node = NamedSharding(mesh, P(NODE_AXIS))
    # the queries as ``MeshPoolRaceTable`` puts them: one packed array,
    # sharded by node, nothing replicated
    packed = pack_queries(np.zeros((n, qcap), np.int32),
                          np.zeros((n, qcap), np.int32),
                          np.zeros((n, qcap, 2), np.int32),
                          np.zeros(NQ, np.int32))
    assert packed.shape == (n, 5, qcap)

    def s(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=by_node)

    compiled = pool_mesh_lookup.lower(
        s((n, -(-nb * 2 * NSLOT // LANES), 1, LANES)),
        s((n, -(-cap // LANES), 1, LANES)),
        s((n, cap, 1, MESH["vdim"]), jnp.float32),
        s(packed.shape), nq=NQ, qcap=qcap, mesh=mesh,
        nslot=MESH["slots_per_bucket"]).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert any(f" {op}(" in text or f" {op}-start(" in text
               for op in ("all-gather", "all-to-all", "all-reduce",
                          "collective-permute"))
    assert any(line.lstrip().startswith("%pool_lookup")
               and 'custom_call_target="tpu_custom_call"' in line
               for line in text.splitlines())
    mem = compiled.memory_analysis()
    per_chip = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes)
    assert 8e9 < per_chip < 16e9
    assert mem.temp_size_in_bytes < 1e9          # no copy of a pool
