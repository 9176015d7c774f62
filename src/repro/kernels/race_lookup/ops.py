"""jit'd public wrappers for the RACE-lookup kernels."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .race_lookup import (TILED_VMEM_BUDGET_BYTES, race_lookup_pallas,
                          race_lookup_pallas_sharded,
                          race_lookup_pallas_tiled, table_vmem_bytes)
from .pool import pool_lookup_pallas
from .ref import pool_lookup_ref, race_lookup_ref


def pallas_kernel(fp_shape, val_shape) -> str:
    """Which kernel ``impl="pallas"`` runs for tables (or, sharded, one
    shard) of these shapes: ``"tiled"`` while the table fits the VMEM
    residency budget, else ``"scalar"`` (per-bucket DMA, no size bound).
    A choice of kernel by size, the same on every backend."""
    if table_vmem_bytes(fp_shape, val_shape) > TILED_VMEM_BUDGET_BYTES:
        return "scalar"
    return "tiled"


@functools.partial(jax.jit,
                   static_argnames=("impl", "qblock"))
def race_lookup(fp_table, val_table, queries, bucket_idx,
                impl: str = "pallas", qblock: int = 64):
    """Batched two-choice hash lookup.

    fp_table (NB, NSLOT) i32, val_table (NB, NSLOT, VDIM), queries (NQ,)
    i32 fingerprints, bucket_idx (NQ, 2) i32 -> (values (NQ, VDIM),
    found (NQ,) i32). The kernels compile for the TPU and run in
    interpret mode on a CPU backend (:func:`repro.kernels.interpret_mode`).

    ``impl``:
      * ``"pallas"`` — the tiled multi-query kernel (QBLOCK queries per
        grid step, MXU one-hot select; ragged tails auto-padded) when the
        tables fit the VMEM-residency budget, else the scalar kernel —
        callers with arbitrarily large tables keep working (see
        :func:`pallas_kernel`),
      * ``"pallas_tiled"`` — force the tiled kernel (caller guarantees the
        tables fit VMEM),
      * ``"pallas_scalar"`` — the one-query-per-step fallback (no VMEM
        table-size bound; the batched_lookup benchmark baseline),
      * ``"ref"`` — the pure-jnp oracle.
    """
    if impl == "ref":
        return race_lookup_ref(fp_table, val_table, queries, bucket_idx)
    if impl == "pallas_scalar" or (
            impl == "pallas"
            and pallas_kernel(fp_table.shape, val_table.shape) == "scalar"):
        return race_lookup_pallas(fp_table, val_table, queries, bucket_idx)
    return race_lookup_pallas_tiled(fp_table, val_table, queries,
                                    bucket_idx, qblock=qblock)


@functools.partial(jax.jit, static_argnames=("nslot", "impl"))
def pool_lookup(index, keys, pool, qkeys, qfps, bucket_idx, *, nslot: int,
                impl: str = "pallas"):
    """RACE's two-level lookup over the pool layout (``PoolRaceTable``):
    both candidate buckets from the index, the KV block of each
    fingerprint match, the record whose stored key is the query key.

    index (ceil(NB * 2 * NSLOT / 128), 1, 128) i32, keys (ceil(CAP /
    128), 1, 128) i32, pool (CAP, 1, VDIM), qkeys (NQ,) i32, qfps (NQ,)
    i32 8-bit fingerprints, bucket_idx (NQ, 2) i32 -> (values (NQ,
    VDIM), found (NQ,) i32, the KV blocks fetched over the batch, an i32
    scalar). ``impl``: ``"pallas"`` (the kernel, index and pool left in
    HBM) or ``"ref"`` (the pure-jnp oracle)."""
    if impl == "ref":
        values, found, blocks = pool_lookup_ref(index, keys, pool, qkeys,
                                                qfps, bucket_idx,
                                                nslot=nslot)
    elif impl == "pallas":
        values, found, blocks = pool_lookup_pallas(index, keys, pool, qkeys,
                                                   qfps, bucket_idx,
                                                   nslot=nslot)
    else:
        raise ValueError(f"unknown impl {impl!r}")
    return values, found, jnp.sum(blocks)


def race_lookup_sharded(fp_tables, val_tables, queries, bucket_idx,
                        shard_idx, impl: str = "pallas", qblock: int = 64):
    """Batched lookup over a SHARDED table set (the dkv shard map).

    fp_tables (NS, NB, NSLOT) i32, val_tables (NS, NB, NSLOT, VDIM),
    queries (NQ,) i32 fingerprints, bucket_idx (NQ, 2) i32 intra-shard
    rows, shard_idx (NQ,) i32 -> (values (NQ, VDIM), found (NQ,) i32).

    ``impl``:
      * ``"pallas"`` — the sharded tiled kernel: grid dimension over
        shards with a per-shard index map, ONE shard's table VMEM-
        resident per step (no all-shards residency bound); shards above
        the VMEM budget take the scalar fallback,
      * ``"pallas_scalar"`` — the scalar fallback, kept: per-shard calls
        into the one-query-per-step kernel (per-bucket DMA, no VMEM
        table-size bound at all),
      * ``"ref"`` — per-shard pure-jnp oracle.

    Not jit-wrapped: the per-shard grouping/scatter is data-dependent
    (the inner pallas_call still executes the kernel body).
    """
    if impl == "pallas" and pallas_kernel(
            fp_tables.shape[1:], val_tables.shape[1:]) == "scalar":
        impl = "pallas_scalar"
    if impl == "pallas":
        return race_lookup_pallas_sharded(fp_tables, val_tables, queries,
                                          bucket_idx, shard_idx,
                                          qblock=qblock)
    if impl not in ("pallas_scalar", "ref"):
        raise ValueError(f"unknown impl {impl!r}")
    q = np.asarray(queries, np.int32)
    b = np.asarray(bucket_idx, np.int32)
    s = np.asarray(shard_idx, np.int64)
    nq = q.shape[0]
    vdim = val_tables.shape[-1]
    out_v = np.zeros((nq, vdim), val_tables.dtype)
    out_f = np.zeros(nq, np.int32)
    for sid in np.unique(s):
        m = s == sid
        if impl == "ref":
            v, f = race_lookup_ref(fp_tables[sid], val_tables[sid],
                                   jnp.asarray(q[m]), jnp.asarray(b[m]))
        else:
            v, f = race_lookup_pallas(fp_tables[sid], val_tables[sid],
                                      jnp.asarray(q[m]),
                                      jnp.asarray(b[m]))
        out_v[m] = np.asarray(v)
        out_f[m] = np.asarray(f)
    return jnp.asarray(out_v), jnp.asarray(out_f)
