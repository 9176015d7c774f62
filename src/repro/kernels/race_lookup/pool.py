"""Pallas TPU kernel: RACE's two-level lookup over an index of 8-byte slots
and a pool of KV blocks, both left in HBM.

RACE hashing (Zuo et al., ATC '21) keeps its buckets apart from its data:
a slot is 8 bytes, an 8-bit fingerprint, an 8-bit length and a 48-bit
pointer to a KV block that carries the key and the value. A read fetches
both candidate buckets, then the KV block of every slot whose fingerprint
matches the key's, and answers with the block whose stored key equals the
key asked for: an 8-bit fingerprint matches falsely about once in 256
occupied slots, so the key comparison is what makes the answer exact.

Layout (see :mod:`repro.kvs.race` ``PoolRaceTable``), as shipped to the
device and read in place:

* ``index`` (ceil(NB * 2 * NSLOT / 128), 1, 128) int32: bucket ``b``'s
  slot ``s`` is the two words at flat offset ``(b * NSLOT + s) * 2``:
  ``hi = fp << 24 | len << 16 | ptr >> 32`` and ``lo = ptr &
  0xFFFFFFFF``. The pointer is a row of the pool. An all-zero slot is
  empty; ``fp`` is never 0, so an occupied slot never reads as empty.
* ``keys`` (ceil(CAP / 128), 1, 128) int32: the key held by each pool
  row (the block's header), at flat offset ``row``.
* ``pool`` (CAP, 1, VDIM): each row one record.

A DMA from HBM moves whole rows of 128 words (the lane tile), so a bucket
is fetched with the seven others of its index row and a block's key with
127 others; the middle axis of 1 keeps every row a tile of its own, with
no padding in HBM.

The kernel takes ``QBLOCK`` queries per grid step and runs the lookup as
three rounds of DMAs from HBM, each round started whole before it is
waited for, so that the copies of a round overlap:

1. the index rows of both candidate buckets of every query of the step
   into SMEM (the bucket numbers ride the scalar-prefetch lane);
2. the key of every block whose slot's fingerprint matches, into SMEM,
   at the pointer that round 1 read: the first level's result sets the
   second level's addresses;
3. for each query, the record of its first matching block whose stored
   key equals the query key, into VMEM, then into the step's output
   block; a query that no block answers gets a zero row and ``found``
   0.

It also returns, per query, the blocks whose key it fetched (true and
false fingerprint matches). Nothing of the index or the pool is copied
or relaid per call: they stay in HBM as they were shipped
(``memory_space=ANY``) and only the buckets and blocks a query needs
are moved.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

#: queries per grid step. SMEM (1 MiB on a v5e) holds the step's index
#: rows (2 x 64 x 512 B), up to 16 key rows a query (512 KiB) and the
#: batch's scalar-prefetched operands (16 B a query)
QBLOCK = 64
#: 32-bit words of one row of the index and key arrays: a DMA from HBM
#: moves whole 128-lane rows
LANES = 128


def _matches(hi, fp):
    """An occupied slot whose 8-bit fingerprint is ``fp``."""
    return (hi != 0) & (jax.lax.shift_right_logical(hi, 24) == fp)


def _pool_kernel(bidx_ref, qkey_ref, qfp_ref, index_hbm, keys_hbm, pool_hbm,
                 out_ref, found_ref, blocks_ref, rows, bkeys, hit_lo, nhit,
                 answer, recs, sems, *, qblock, nslot):
    base = pl.program_id(0) * qblock
    words = 2 * nslot                    # int32 words of one bucket
    per_row = LANES // words             # buckets in one index row
    cand = 2 * nslot                     # candidate slots of one query

    def bucket_copy(j, row):
        return pltpu.make_async_copy(index_hbm.at[row], rows.at[pl.ds(j, 1)],
                                     sems.at[0])

    def key_copy(j, row):
        return pltpu.make_async_copy(keys_hbm.at[row], bkeys.at[pl.ds(j, 1)],
                                     sems.at[1])

    def block_copy(i, row):
        return pltpu.make_async_copy(pool_hbm.at[row], recs.at[i], sems.at[2])

    def wait_all(copy, n):
        def body(_, c):
            copy(0, 0).wait()
            return c
        jax.lax.fori_loop(0, n, body, 0)

    # round 1: the index row that holds each candidate bucket
    def start_buckets(j, c):
        bucket_copy(j, bidx_ref[2 * base + j] // per_row).start()
        return c
    jax.lax.fori_loop(0, 2 * qblock, start_buckets, 0)
    wait_all(bucket_copy, 2 * qblock)

    # round 2: the key row of every block whose slot's fingerprint matches;
    # query i's k-th match keeps its pointer in hit_lo[i * cand + k]
    def start_keys(i, n):
        fp = qfp_ref[base + i]
        m = 0
        for b in range(2):
            j = 2 * i + b
            w0 = (bidx_ref[2 * base + j] % per_row) * words
            for s in range(nslot):
                hi, lo = rows[j, w0 + 2 * s], rows[j, w0 + 2 * s + 1]
                hit = _matches(hi, fp)

                @pl.when(hit)
                def _():
                    hit_lo[i * cand + m] = lo
                    key_copy(i * cand + m, lo // LANES).start()
                m = m + hit.astype(jnp.int32)
        nhit[i] = m
        blocks_ref[pl.ds(i, 1), :] = jnp.full((1, 1), m, jnp.int32)
        return n + m
    fetched = jax.lax.fori_loop(0, qblock, start_keys, 0)
    wait_all(key_copy, fetched)

    # round 3: the record of the first matching block whose stored key is
    # the query key
    def start_records(i, n):
        key = qkey_ref[base + i]

        def per_match(k, row):
            lo = hit_lo[i * cand + k]
            same = bkeys[i * cand + k, lo % LANES] == key
            return jnp.where((row < 0) & same, lo, row)

        row = jax.lax.fori_loop(0, nhit[i], per_match, -1)
        answer[i] = row

        @pl.when(row >= 0)
        def _():
            block_copy(i, row).start()

        found_ref[pl.ds(i, 1), :] = jnp.full((1, 1), (row >= 0).astype(
            jnp.int32), jnp.int32)
        return n + (row >= 0).astype(jnp.int32)
    answered = jax.lax.fori_loop(0, qblock, start_records, 0)
    wait_all(block_copy, answered)

    def put(i, c):
        out_ref[pl.ds(i, 1), :] = jnp.where(answer[i] >= 0, recs[i],
                                            jnp.zeros_like(recs[i]))
        return c
    jax.lax.fori_loop(0, qblock, put, 0)


def pool_lookup_pallas(index, keys, pool, qkeys, qfps, bucket_idx, *,
                       nslot: int, interpret: bool | None = None):
    """index (ceil(NB * 2 * NSLOT / 128), 1, 128) int32, keys (ceil(CAP /
    128), 1, 128) int32, pool (CAP, 1, VDIM); qkeys (NQ,) int32 query
    keys, qfps (NQ,) int32 their 8-bit fingerprints (1..255), bucket_idx
    (NQ, 2) int32 candidate buckets.

    Returns (values (NQ, VDIM), found (NQ,) int32, blocks (NQ,) int32 the
    KV blocks fetched per query). NQ is padded to a multiple of the step
    (``QBLOCK``, or NQ rounded up to 8 if less) with null queries
    (fingerprint 0 matches no occupied slot), sliced off the outputs."""
    vdim = pool.shape[-1]
    nq = qkeys.shape[0]
    if nq == 0:
        return (jnp.zeros((0, vdim), pool.dtype), jnp.zeros((0,), jnp.int32),
                jnp.zeros((0,), jnp.int32))
    qblock = min(QBLOCK, -(-nq // 8) * 8)
    pad = (-nq) % qblock
    if pad:
        qkeys = jnp.pad(qkeys, (0, pad))
        qfps = jnp.pad(qfps, (0, pad))
        bucket_idx = jnp.pad(bucket_idx, ((0, pad), (0, 0)))
    nq_pad = nq + pad
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    cand = 2 * nslot
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nq_pad // qblock,),
        in_specs=[hbm, hbm, hbm],
        out_specs=[
            pl.BlockSpec((qblock, vdim), lambda i, *_: (i, 0)),
            pl.BlockSpec((qblock, 1), lambda i, *_: (i, 0)),
            pl.BlockSpec((qblock, 1), lambda i, *_: (i, 0)),
        ],
        scratch_shapes=[
            pltpu.SMEM((2 * qblock, LANES), jnp.int32),
            pltpu.SMEM((qblock * cand, LANES), jnp.int32),
            pltpu.SMEM((qblock * cand,), jnp.int32),
            pltpu.SMEM((qblock,), jnp.int32),
            pltpu.SMEM((qblock,), jnp.int32),
            pltpu.VMEM((qblock, 1, vdim), pool.dtype),
            pltpu.SemaphoreType.DMA((3,)),
        ],
    )
    values, found, blocks = pl.pallas_call(
        functools.partial(_pool_kernel, qblock=qblock, nslot=nslot),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nq_pad, vdim), pool.dtype),
            jax.ShapeDtypeStruct((nq_pad, 1), jnp.int32),
            jax.ShapeDtypeStruct((nq_pad, 1), jnp.int32),
        ],
        name="pool_lookup",
        interpret=interpret_mode() if interpret is None else interpret,
    )(bucket_idx.reshape(2 * nq_pad), qkeys, qfps, index, keys, pool)
    return values[:nq], found[:nq, 0], blocks[:nq, 0]
