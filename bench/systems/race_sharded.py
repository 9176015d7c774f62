"""The dkv shard map on one chip: ``ShardedDeviceRaceTable``, loaded
through its ``insert`` and served by its own ``lookup_batch`` (host
hashing and shard routing, the sharded Pallas kernel, the grouping and
the scatter back to input order)."""

from __future__ import annotations

import inspect

import numpy as np

from repro.kernels.race_lookup.ops import race_lookup_sharded
from repro.kernels.race_lookup.race_lookup import (group_by_shard,
                                                   sharded_lookup_call)
from repro.kvs.race import ShardedDeviceRaceTable

#: the tile size ``lookup_batch`` runs the sharded kernel with
QBLOCK = inspect.signature(race_lookup_sharded).parameters["qblock"].default


def build(config: dict, keys: np.ndarray, values: np.ndarray):
    table = ShardedDeviceRaceTable(
        n_shards=config["n_shards"], n_buckets=config["buckets_per_shard"],
        nslot=config["slots_per_bucket"], vdim=config["vdim"])
    for k, v in zip(keys.tolist(), values):
        table.insert(k, v)
    return table


def multiget(table, keys: np.ndarray):
    """One multi-get as a client makes it: the answers back on the host."""
    v, f = table.lookup_batch(keys)
    return np.asarray(v), np.asarray(f)


def _shape(table, busiest: int) -> tuple[int, int]:
    """(padded queries per shard, tile) that the program compiles the
    kernel for when the busiest shard of a batch gets ``busiest`` keys."""
    q_g, _, _, qblock = group_by_shard(
        np.zeros(busiest, np.int32), np.zeros((busiest, 2), np.int32),
        np.zeros(busiest, np.int64), table.n_shards, QBLOCK)
    return q_g.shape[1], qblock


def warm_up(table, batches) -> list:
    """Compile (or load from the cache) the kernel for every shape the
    traffic can bring, so that a window's fresh draws find their shape
    warm: each padded size from the one for half a tile fewer keys than
    the sample ``batches`` put on their least busy busiest shard, to the
    next size above the one for their most. The kernel runs on zero
    tables of the table's shapes on the device, as ``lookup_batch`` calls
    it; then one multi-get warms the whole entry."""
    import jax.numpy as jnp

    busiest = [int(np.bincount([table.shard_of(k) for k in b.tolist()],
                               minlength=table.n_shards).max())
               for b in batches]
    top = _shape(table, max(busiest))[0]
    even = -(-len(batches[0]) // table.n_shards)
    shapes, count = [], max(even, min(busiest) - QBLOCK // 2, 1)
    while not shapes or shapes[-1][0] <= top:
        shapes.append(_shape(table, count))
        count = shapes[-1][0] + 1
    fp, val = table.shards[0].tables()
    fp_t = jnp.zeros((table.n_shards, *fp.shape), fp.dtype)
    val_t = jnp.zeros((table.n_shards, *val.shape), val.dtype)
    for qcap, qblock in shapes:
        out = sharded_lookup_call(
            fp_t, val_t, jnp.zeros((table.n_shards, qcap), jnp.int32),
            jnp.zeros((table.n_shards, qcap, 2), jnp.int32),
            qblock=qblock, interpret=None)
        for o in out:
            o.block_until_ready()
    del fp_t, val_t, out
    multiget(table, batches[0])
    return shapes
