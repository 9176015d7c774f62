"""RACE hashing's single table on one chip: ``DeviceRaceTable``, loaded
through its ``insert`` and served by its own ``lookup_batch`` (host
hashing, then the lookup kernel that the table's size selects)."""

from __future__ import annotations

import numpy as np

from repro.kvs.race import DeviceRaceTable


def build(config: dict, keys: np.ndarray, values: np.ndarray):
    table = DeviceRaceTable(n_buckets=config["buckets"],
                            nslot=config["slots_per_bucket"],
                            vdim=config["vdim"])
    for k, v in zip(keys.tolist(), values):
        table.insert(k, v)
    return table


def multiget(table, keys: np.ndarray):
    """One multi-get as a client makes it: the answers back on the host."""
    v, f = table.lookup_batch(keys)
    return np.asarray(v), np.asarray(f)


def warm_up(table, batches) -> list:
    """The kernel's shapes depend on the batch length alone: one
    multi-get of each length compiles (or loads) all the window uses."""
    lengths = sorted({len(b) for b in batches})
    for n in lengths:
        multiget(table, next(b for b in batches if len(b) == n))
    return lengths
