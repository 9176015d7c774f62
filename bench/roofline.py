"""The yardstick of the lookup kernels: the chip's peaks and the least
work that any implementation of a multi-get needs.

A multi-get of NQ keys over a two-choice table of NSLOT-slot buckets has,
at the least, to read the key of each query, the NSLOT fingerprints of
every distinct candidate bucket of the batch once (both of a key's
buckets, as RACE's fingerprint-first read fetches them), the value of
every distinct key it finds once, and to write every answer once (its
value and its found flag). The fingerprint compares are no FLOPs worth
counting, so the lookup is memory-bound: its least time is the least
bytes over the chip's HBM bandwidth. Bytes that a kernel moves beyond
these (whole shards or buckets of values streamed through VMEM, padded
tiles, query operands wider than the key) are its own inefficiency and
are not counted, so a kernel that moves less reads a higher share and
none can read above 100%.

The candidate buckets are placed by the yardstick's own copy of the
table's two-choice hashes (``kvs/race.py`` ``_h1``, ``_h2`` and
``shard_of_key``), so a program that changes its hashing does not move
the count: for random placement the distinct-bucket count, and with it
the bytes, changes by the few buckets that keys share.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"

FP_BYTES = 4          # one int32 fingerprint
FOUND_BYTES = 4       # one int32 found flag
KEY_BYTES = 4         # one query key (keys are below 2^30)
_MASK32 = np.uint64(0xFFFFFFFF)


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; a kind that
    the table does not list is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind.startswith("_") or device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}")
    return table[device_kind]


def candidate_rows(keys, config: dict) -> np.ndarray:
    """(NQ, 2) each key's two candidate buckets as global rows of the
    table the configuration describes: ``buckets`` for one table, or
    ``n_shards`` x ``buckets_per_shard`` (row ``shard * buckets_per_shard
    + bucket``) for a shard map."""
    k = np.asarray(keys, np.int64).astype(np.uint64)
    nb = config.get("buckets_per_shard", config.get("buckets"))
    nb = np.uint64(nb)
    h1 = (k * np.uint64(2654435761) + np.uint64(7)) % nb
    h2 = (((k * np.uint64(0x85EBCA6B) + np.uint64(0x9E3779B9)) & _MASK32)
          >> np.uint64(8)) % nb
    rows = np.stack([h1, h2], axis=1)
    if "n_shards" in config:
        shard = (((k * np.uint64(0x9E3779B1) + np.uint64(0x85EBCA77))
                  & _MASK32) % np.uint64(config["n_shards"]))
        rows = rows + (shard * nb)[:, None]
    return rows.astype(np.int64)


def least_bytes(bucket_rows, found_keys: int, n_queries: int, *,
                nslot: int, vdim: int, value_itemsize: int = 4) -> int:
    """Least HBM bytes of one multi-get.

    ``bucket_rows`` (NQ, 2): each key's two candidate buckets as global
    rows of the table, so the count does not depend on how an
    implementation routes or groups the keys; a bucket that several keys
    share is read once. ``found_keys``: the distinct keys of the batch
    that the table holds, each value read once. ``n_queries``: every
    query, asked twice or not, reads its key and writes its answer."""
    buckets = np.unique(np.asarray(bucket_rows)).size
    read = (buckets * nslot * FP_BYTES
            + found_keys * vdim * value_itemsize
            + n_queries * KEY_BYTES)
    write = n_queries * (vdim * value_itemsize + FOUND_BYTES)
    return int(read + write)


def multiget_least_bytes(keys, loaded_sorted: np.ndarray,
                         config: dict) -> int:
    """``least_bytes`` of the multi-get ``keys`` on the configuration's
    table, which holds the keys of the sorted array ``loaded_sorted``."""
    keys = np.asarray(keys, np.int64)
    distinct = np.unique(keys)
    at = np.searchsorted(loaded_sorted, distinct).clip(
        0, len(loaded_sorted) - 1)
    found = int(np.count_nonzero(loaded_sorted[at] == distinct))
    return least_bytes(candidate_rows(distinct, config), found, len(keys),
                       nslot=config["slots_per_bucket"],
                       vdim=config["vdim"])


def least_seconds(nbytes: float, device_kind: str) -> tuple[float, str]:
    """(least time, the bound that sets it) of ``nbytes`` of memory-bound
    work on one chip of ``device_kind``."""
    return nbytes / peaks(device_kind)["hbm_bytes_per_s"], "memory"
