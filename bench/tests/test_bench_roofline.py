"""The least-bytes count of a multi-get and the table of peaks."""

import numpy as np
import pytest

from bench import roofline

NSLOT, VDIM = 8, 256
FPS = NSLOT * 4                          # one bucket's fingerprints
VALUE = VDIM * 4                         # one record, read once
ANSWER = VDIM * 4 + 4                    # value and found flag, written
KEY = 4                                  # one query key


def test_each_distinct_candidate_bucket_is_read_once():
    rows = np.array([[3, 9], [9, 4], [3, 9], [7, 7]])
    # distinct buckets 3, 4, 7, 9; three keys found; four queries
    assert roofline.least_bytes(rows, 3, 4, nslot=NSLOT, vdim=VDIM) == (
        4 * FPS + 3 * VALUE + 4 * (KEY + ANSWER))


def test_the_count_does_not_depend_on_sharded_or_flat_routing():
    """A sharded table's candidate buckets, counted shard by shard as the
    sharded kernel groups them, and the same buckets addressed as global
    rows of one flat table, give one byte count."""
    rng = np.random.default_rng(0)
    ns, nb, nq = 7, 31, 500
    shard = rng.integers(0, ns, nq)
    bidx = rng.integers(0, nb, (nq, 2))
    flat = shard[:, None] * nb + bidx
    per_shard = sum(np.unique(bidx[shard == s]).size for s in range(ns))
    assert np.unique(flat).size == per_shard
    want = per_shard * FPS + 400 * VALUE + nq * (KEY + ANSWER)
    assert roofline.least_bytes(flat, 400, nq, nslot=NSLOT,
                                vdim=VDIM) == want
    # the order the keys come in (grouped per shard, or as asked) is moot
    order = np.argsort(shard, kind="stable")
    assert roofline.least_bytes(flat[order], 400, nq, nslot=NSLOT,
                                vdim=VDIM) == want


def test_a_full_multiget_of_distinct_keys():
    """4096 distinct loaded keys: both buckets' fingerprints, one record
    each, every answer written: about 8.7 MB, memory-bound."""
    rows = np.arange(8192).reshape(4096, 2)
    nbytes = roofline.least_bytes(rows, 4096, 4096, nslot=NSLOT, vdim=VDIM)
    assert nbytes == 8192 * FPS + 4096 * (VALUE + KEY + ANSWER)
    assert 8.6e6 < nbytes < 8.7e6
    seconds, bound = roofline.least_seconds(nbytes, "TPU v5 lite")
    assert bound == "memory"
    assert seconds == pytest.approx(nbytes / 819e9)


def _rows_by_python_ints(key, config):
    """The table's placement worked in Python's unbounded integers."""
    nb = config.get("buckets_per_shard", config.get("buckets"))
    h1 = (key * 2654435761 + 7) % nb
    h2 = (((key * 0x85EBCA6B + 0x9E3779B9) & 0xFFFFFFFF) >> 8) % nb
    base = 0
    if "n_shards" in config:
        base = ((key * 0x9E3779B1 + 0x85EBCA77) & 0xFFFFFFFF) \
            % config["n_shards"] * nb
    return [base + h1, base + h2]


@pytest.mark.parametrize("config", [
    {"buckets": 262139},
    {"n_shards": 251, "buckets_per_shard": 1021}])
def test_candidate_rows_place_keys_without_overflow(config):
    keys = np.random.default_rng(4).integers(1, 2 ** 30, 2000)
    keys[:2] = [1, 2 ** 30 - 1]
    rows = roofline.candidate_rows(keys, config)
    assert rows.tolist() == [_rows_by_python_ints(int(k), config)
                             for k in keys]
    total = config.get("buckets") or (config["n_shards"]
                                      * config["buckets_per_shard"])
    assert rows.min() >= 0 and rows.max() < total


def test_a_multiget_reads_each_found_record_once():
    config = {"n_shards": 3, "buckets_per_shard": 257,
              "slots_per_bucket": NSLOT, "vdim": VDIM}
    loaded = np.array([5, 11, 40, 77, 123])
    keys = np.array([11, 11, 40, 999, 5, 40])     # 999 was never loaded
    rows = roofline.candidate_rows(np.unique(keys), config)
    want = (np.unique(rows).size * FPS + 3 * VALUE
            + len(keys) * (KEY + ANSWER))
    assert roofline.multiget_least_bytes(keys, loaded, config) == want


def test_peaks_are_keyed_by_device_kind():
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes"] == 16e9
    for kind in ("cpu", "TPU v4", "_source"):
        with pytest.raises(KeyError):
            roofline.peaks(kind)
