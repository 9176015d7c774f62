"""The plain reference of a key-value read: a host dictionary from each
inserted key to its record. It imports nothing of the program and takes
nothing the program made: only the keys and records the benchmark drew."""

from __future__ import annotations

import numpy as np


class KVIndex:
    def __init__(self, keys: np.ndarray, values: np.ndarray):
        self.row = {k: i for i, k in enumerate(keys.tolist())}
        self.values = values

    def get_many(self, keys: np.ndarray):
        """(records (n, vdim), found (n,) bool): a key never inserted
        reads as not found with an all-zero record."""
        rows = np.array([self.row.get(k, -1) for k in keys.tolist()],
                        np.int64)
        found = rows >= 0
        vals = np.where(found[:, None], self.values[np.maximum(rows, 0)],
                        np.float32(0))
        return vals, found


def build(keys: np.ndarray, values: np.ndarray) -> KVIndex:
    return KVIndex(keys, values)
