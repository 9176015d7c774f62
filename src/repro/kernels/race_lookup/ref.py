"""Pure-jnp oracle for the RACE-hash lookup kernel."""

from __future__ import annotations

import jax.numpy as jnp


def race_lookup_ref(fp_table, val_table, queries, bucket_idx):
    """Same contract as race_lookup_pallas (first matching slot wins;
    bucket 1's slots order before bucket 2's)."""
    fps = jnp.concatenate(
        [fp_table[bucket_idx[:, 0]], fp_table[bucket_idx[:, 1]]],
        axis=1)                                          # (NQ, 2*NSLOT)
    vals = jnp.concatenate(
        [val_table[bucket_idx[:, 0]], val_table[bucket_idx[:, 1]]],
        axis=1)                                          # (NQ, 2*NSLOT, V)
    hit = (fps == queries[:, None]) & (fps != 0)
    first = jnp.argmax(hit, axis=1)
    found = jnp.any(hit, axis=1)
    # a gather, not a one-hot matmul: exact on every backend, whatever
    # precision the device's matmul uses for f32
    picked = jnp.take_along_axis(vals, first[:, None, None], axis=1)[:, 0]
    values = jnp.where(found[:, None], picked, 0).astype(vals.dtype)
    found = found.astype(jnp.int32)
    return values, found


def make_table(n_buckets: int, nslot: int, vdim: int, keys, values,
               seed: int = 7):
    """Build (fp_table, val_table, bucket_idx_fn) from int32 keys/values.

    Two-choice hashing like RACE: each key has two candidate buckets; the
    less-loaded one receives it (host-side build; device-side lookup).
    """
    import numpy as np
    fp_table = np.zeros((n_buckets, nslot), np.int32)
    val_table = np.zeros((n_buckets, nslot, vdim), np.float32)

    def h1(k):
        return (k * 2654435761 + seed) % n_buckets

    def h2(k):
        return (k * 40503 + 0x9E3779B9 + seed) % n_buckets

    def fingerprint(k):
        fp = (k * 2246822519 + 1) & 0x7FFFFFFF
        return fp if fp != 0 else 1

    loads = np.zeros(n_buckets, np.int32)
    for k, v in zip(keys, values):
        b1, b2 = int(h1(k)), int(h2(k))
        b = b1 if loads[b1] <= loads[b2] else b2
        if loads[b] >= nslot:
            b = b2 if b == b1 else b1
            if loads[b] >= nslot:
                raise RuntimeError("bucket overflow; grow table")
        fp_table[b, loads[b]] = fingerprint(k)
        val_table[b, loads[b]] = v
        loads[b] += 1

    def query_prep(qkeys):
        qk = np.asarray(qkeys)
        bidx = np.stack([h1(qk), h2(qk)], axis=1).astype(np.int32)
        fps = ((qk * 2246822519 + 1) & 0x7FFFFFFF).astype(np.int32)
        fps = np.where(fps == 0, 1, fps)
        return fps, bidx

    return fp_table, val_table, query_prep
