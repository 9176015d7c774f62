"""The yardstick of the pool-layout lookup (``race-pool-*``
configurations): the least HBM bytes that any implementation of a
multi-get needs when it reads as RACE reads, over an index of 8-byte
slots and a pool of KV blocks that carry their keys.

A multi-get of NQ keys has, at the least, to read every distinct
candidate bucket of the batch once (NSLOT slots of 8 B), the 4 B key of
every distinct block whose slot's 8-bit fingerprint matches a key asked
for (true and false matches: the fingerprint cannot tell them apart), the
record of every distinct key found once, and 4 B of key per query, and to
write every answer once (its record and a 4 B found flag). The lookup is
memory-bound; its least time is these bytes over the chip's HBM bandwidth
(``bench/roofline.py``'s peaks). What a kernel moves beyond them (whole
128-word rows around a bucket or a key, padded tiles, query operands
wider than the key) is its own waste.

The candidate buckets and the query's fingerprint come from the
yardstick's own copy of the table's hashes (``kvs/race.py`` ``_h1``,
``_h2``, ``_fp`` and ``fp8``). Which blocks sit in a key's candidate
buckets is the table's state after its inserts (two-choice placement
depends on the order of the inserts): it is read from the index the table
holds, decoded here from RACE's slot format, ``hi = fp8 << 24 | len << 16
| ptr >> 32``, ``lo = ptr & 0xFFFFFFFF``.
"""

from __future__ import annotations

import numpy as np

from bench import roofline

SLOT_BYTES = 8
KEY_BYTES = 4
FOUND_BYTES = 4
_MASK32 = np.uint64(0xFFFFFFFF)


def fingerprints(keys) -> np.ndarray:
    """Each key's 8-bit slot fingerprint (1..255)."""
    k = np.asarray(keys, np.int64).astype(np.uint64)
    fp31 = ((k * np.uint64(2246822519) + np.uint64(1)) & _MASK32) \
        & np.uint64(0x7FFFFFFF)
    fp31 = np.where(fp31 == 0, 1, fp31)
    return np.maximum((fp31 >> np.uint64(23)) & np.uint64(0xFF), 1).astype(
        np.int64)


def decode(index: np.ndarray, n_buckets: int, nslot: int):
    """(fp8 (NB, NSLOT), pointer (NB, NSLOT)) of the index's slots; an
    empty slot reads fingerprint 0."""
    words = index.reshape(-1)[:n_buckets * nslot * 2].reshape(
        n_buckets, nslot, 2).astype(np.int64) & 0xFFFFFFFF
    hi, lo = words[..., 0], words[..., 1]
    return hi >> 24, ((hi & 0xFFFF) << 32) | lo


def least_bytes(bucket_rows, matched_blocks: int, found_keys: int,
                n_queries: int, *, nslot: int, vdim: int,
                value_itemsize: int = 4) -> int:
    """Least HBM bytes of one multi-get: ``bucket_rows`` (NQ, 2) the
    candidate buckets (each distinct one read once), ``matched_blocks``
    the distinct blocks whose fingerprint matches a key asked for (their
    keys read once), ``found_keys`` the distinct keys found (their
    records read once), ``n_queries`` every query (its key read and its
    answer written)."""
    buckets = np.unique(np.asarray(bucket_rows)).size
    read = (buckets * nslot * SLOT_BYTES
            + matched_blocks * KEY_BYTES
            + found_keys * vdim * value_itemsize
            + n_queries * KEY_BYTES)
    write = n_queries * (vdim * value_itemsize + FOUND_BYTES)
    return int(read + write)


def multiget_least_bytes(keys, loaded_sorted: np.ndarray, slots,
                         config: dict) -> int:
    """``least_bytes`` of the multi-get ``keys`` on the configuration's
    table, which holds the keys of the sorted array ``loaded_sorted`` in
    the slots ``slots`` (``decode``'s pair)."""
    keys = np.asarray(keys, np.int64)
    distinct = np.unique(keys)
    at = np.searchsorted(loaded_sorted, distinct).clip(
        0, len(loaded_sorted) - 1)
    found = int(np.count_nonzero(loaded_sorted[at] == distinct))
    rows = roofline.candidate_rows(distinct, config)
    fp, ptr = slots
    hit = fp[rows] == fingerprints(distinct)[:, None, None]
    matched = np.unique(ptr[rows][hit]).size
    return least_bytes(rows, matched, found, len(keys),
                       nslot=config["slots_per_bucket"],
                       vdim=config["vdim"])
