"""RACE hashing's published layout on one chip: ``PoolRaceTable`` (an
index of 8-byte slots over a pool of KV blocks), loaded through its
``insert_many`` and served by its own ``lookup_batch`` (host hashing,
then the two-level pool kernel). A multi-get and the warm-up are the
flat table's: the kernel's shapes depend on the batch length alone."""

from __future__ import annotations

import numpy as np

from bench.systems.race_flat import multiget, warm_up  # noqa: F401
from repro.kvs.race import PoolRaceTable


def build(config: dict, keys: np.ndarray, values: np.ndarray):
    table = PoolRaceTable(n_buckets=config["buckets"],
                          nslot=config["slots_per_bucket"],
                          vdim=config["vdim"],
                          capacity=config["recordcount"])
    table.insert_many(keys, values)
    return table
