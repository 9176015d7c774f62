"""RACE's pool over several memory nodes, one per chip: ``MeshPoolRaceTable``
(one index and KV-block pool per node, on a mesh of the cell's chips),
loaded through its ``insert_many`` and served by its own
``lookup_batch`` (host hashing and node routing, grouping per node, one
SPMD program that runs the pool kernel on every node and gathers the
answers into the keys' order on the chips). A multi-get is the flat
table's: the answers copied to the host."""

from __future__ import annotations

import numpy as np

from bench.systems.race_flat import multiget
from repro.kernels.race_lookup.pool import QBLOCK
from repro.kernels.race_lookup.race_lookup import group_by_shard
from repro.kvs.race import MeshPoolRaceTable, shard_of_keys


def build(config: dict, keys: np.ndarray, values: np.ndarray):
    """The table on the first ``nodes`` devices (a device holds several
    whole nodes where there are fewer), each node's pool as large as the
    busiest node's share of ``keys``."""
    import jax

    n = config["nodes"]
    table = MeshPoolRaceTable(
        n_nodes=n, n_buckets=config["buckets"],
        nslot=config["slots_per_bucket"], vdim=config["vdim"],
        capacity=int(np.bincount(shard_of_keys(keys, n), minlength=n).max()),
        devices=jax.devices()[:n])
    table.insert_many(keys, values)
    return table


def _qcap(table, busiest: int) -> int:
    """Padded queries per node that the program runs when the busiest
    node of a batch gets ``busiest`` keys."""
    q_g, _, _, _ = group_by_shard(
        np.zeros(busiest, np.int32), np.zeros((busiest, 2), np.int32),
        np.zeros(busiest, np.int64), table.n_nodes, QBLOCK)
    return q_g.shape[1]


def warm_up(table, batches) -> list:
    """One multi-get (which ships the table), then the whole program,
    kernel and gather, for every padded size a window's fresh draws can
    bring: from the one for half a tile fewer keys than the sample
    ``batches`` put on their least busy busiest node, to the next size
    above the one for their most. It runs on the resident tables with
    null queries, as ``lookup_batch`` calls it."""
    multiget(table, batches[0])
    n = table.n_nodes
    busiest = [int(np.bincount(shard_of_keys(b, n), minlength=n).max())
               for b in batches]
    top = _qcap(table, max(busiest))
    even = -(-len(batches[0]) // n)
    shapes, count = [], max(even, min(busiest) - QBLOCK // 2, 1)
    while not shapes or shapes[-1] <= top:
        shapes.append(_qcap(table, count))
        count = shapes[-1] + 1
    inv = np.zeros(len(batches[0]), np.int32)
    for qcap in shapes:
        null = np.zeros((n, qcap), np.int32)
        for out in table.lookup_grouped(null, null,
                                        np.zeros((n, qcap, 2), np.int32),
                                        inv):
            out.block_until_ready()
    return shapes
