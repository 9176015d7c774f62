"""The pool kernel's share of its roofline over a pool of memory nodes
laid out on several chips (``MeshPoolRaceTable``): the least time any
implementation needs for the window's multi-gets over the kernel's mean
device time per chip.

Each key is read on its own node, so the least bytes of a multi-get are
the sum over the nodes of ``bench/pool_roofline.py``'s least bytes of
the keys routed to that node, on that node's own index (decoded from
RACE's slot format) and loaded keys. The least time spreads those bytes
over the chips' HBM: their sum over ``chips`` x the bandwidth of one
(``bench/roofline.py``'s peaks), with ``chips`` the trace's device
planes. Each chip's kernel time is at least its own nodes' least time,
so the share cannot pass 100%. Keys are routed by the yardstick's own
copy of the dkv shard map (``kvs/race.py`` ``shard_of_keys``). None
where the trace holds no pool kernel or the table holds no nodes."""

from __future__ import annotations

import numpy as np

from bench import pool_roofline, roofline
from bench.metrics.pool_lookup_kernel_ms import kernel_ns

_MASK32 = np.uint64(0xFFFFFFFF)


def node_of(keys, n_nodes: int) -> np.ndarray:
    """Each key's memory node under the dkv shard map."""
    k = np.asarray(keys, np.int64).astype(np.uint64)
    return ((k * np.uint64(0x9E3779B1) + np.uint64(0x85EBCA77)) & _MASK32) \
        % np.uint64(n_nodes)


def least_bytes(batches, loaded_sorted: np.ndarray, node_slots,
                config: dict) -> int:
    """Least HBM bytes of the multi-gets ``batches`` on a pool whose node
    ``i`` holds the keys of ``loaded_sorted`` (sorted) that the shard map
    sends it, in the slots ``node_slots[i]`` (``pool_roofline.decode``'s
    pair); ``config`` gives one node's geometry."""
    n = len(node_slots)
    owner = node_of(loaded_sorted, n)
    # a node that holds no key finds none: -1 is never a key
    loaded = [loaded_sorted[owner == i] if np.any(owner == i)
              else np.array([-1]) for i in range(n)]
    total = 0
    for keys in batches:
        keys = np.asarray(keys, np.int64)
        at = node_of(keys, n)
        for i in range(n):
            mine = keys[at == i]
            if len(mine):
                total += pool_roofline.multiget_least_bytes(
                    mine, loaded[i], node_slots[i], config)
    return total


def read(run):
    k = kernel_ns(run)
    nodes = getattr(run.table, "nodes", None)
    if k is None or not nodes:
        return None
    config = run.cell.config
    slots = [pool_roofline.decode(node.tables()[0], config["buckets"],
                                  config["slots_per_bucket"])
             for node in nodes]
    nbytes = least_bytes(run.batches, run.loaded, slots, config)
    least_s, _ = roofline.least_seconds(nbytes, run.device_kind)
    chips = len(run.trace.device_ops)
    return 100.0 * least_s / chips / (k[0] / 1e9)
