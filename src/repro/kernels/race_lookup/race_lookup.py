"""Pallas TPU kernels: batched RACE-hash lookup ("one-sided READ" analogue).

The meta server / DrTM-KV of the paper serves lookups with one one-sided
RDMA READ, bypassing the remote CPU. On TPU the table lives in device HBM
and the lookup is a gather: for each query, fetch its TWO candidate buckets
(RACE extendible hashing), compare fingerprints against all slots, and
select the matching value row — one fused kernel, no host round-trip.

Two kernels live here, one per inline table layout:

``race_lookup_pallas`` (the flat table)
    One query per grid step. Its scalar-prefetch BlockSpecs DMA exactly
    the two candidate buckets per step, so it has no VMEM table-size
    bound; the query fingerprint is read from the scalar-prefetch lane
    too.

``race_lookup_pallas_sharded`` (the dkv shard map)
    Per-shard tables stacked as ``(NS, NB, NSLOT)`` / ``(NS, NB, NSLOT,
    VDIM)`` and a **per-shard index map**: the grid's leading dimension
    is the shard and each grid step's BlockSpec selects ONLY that shard's
    table, so VMEM holds one shard at a time (at most
    ``SHARD_VMEM_BUDGET_BYTES``). ``QBLOCK`` queries per grid step: the
    bucket rows ride the scalar-prefetch lane (SMEM); the tile's 2*QBLOCK
    candidate buckets are copied row by row out of the VMEM-resident
    shard into a VMEM scratch tile, the fingerprint compare runs
    vectorized over the whole ``(QBLOCK, NSLOT)`` tile on the VPU, and
    the value select is a one-hot ``(QBLOCK, QBLOCK*2*NSLOT) @
    (QBLOCK*2*NSLOT, VDIM)`` contraction so the MXU engages (QBLOCK 64
    with 8-slot buckets makes it a (64, 1024) @ (1024, VDIM) matmul). The
    contraction runs at HIGHEST precision: a one-hot row then returns the
    stored f32 value bit-exact. Queries are grouped per shard host-side
    (:func:`group_by_shard`), padded to the tile size with null queries
    (fingerprint 0 matches nothing), run through :func:`sharded_lookup_call`
    (a pure function of shapes) and gathered into input order on the
    device (:func:`in_key_order`). The minor grid dimension iterates tiles
    within a shard, so consecutive steps reuse the resident shard block.

Tile rule: every block's last two dims are multiples of (8, 128) or equal
the array's, so the scalar kernel and the per-query blocks use 3-D views
with a singleton middle axis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.kernels import interpret_mode

#: padded VMEM bytes (see :func:`table_vmem_bytes`) of the largest shard
#: the sharded kernel pins VMEM-resident for a grid step
SHARD_VMEM_BUDGET_BYTES = 16 * 1024 * 1024
#: scoped VMEM the sharded kernel requests: two pipeline buffers of a
#: budget-sized shard (32 MiB) plus the gathered value tile, one-hot and
#: output blocks (< 8 MiB at VDIM <= 1024). Above v5e's 16 MiB default
#: scoped limit, well under its 128 MiB of VMEM.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _rup(x: int, m: int) -> int:
    return -(-x // m) * m


def table_vmem_bytes(fp_shape, val_shape) -> int:
    """VMEM bytes of one shard's 32-bit (fp, val) table pair once the last
    two dims of each are padded to the (8, 128) tile — the NSLOT-wide
    fingerprint rows take a full 128-lane row each."""
    nb, nslot = fp_shape[-2:]
    vdim = val_shape[-1]
    return 4 * (_rup(nb, 8) * _rup(nslot, 128)
                + nb * _rup(nslot, 8) * _rup(vdim, 128))


# ------------------------------------------------------------ flat table
def _first_hit(fps, q, slot, nslot):
    """Per row, the candidate index of the first slot whose fingerprint
    matches ``q`` (2*NSLOT when none does). ``slot`` numbers the columns
    of ``fps``; empty slots (fingerprint 0) never match."""
    hit = (fps == q) & (fps != 0)
    return jnp.min(jnp.where(hit, slot, 2 * nslot), axis=1, keepdims=True)


def _lookup_kernel(bidx_ref, q_ref, fp1_ref, fp2_ref, val1_ref, val2_ref,
                   out_ref, found_ref, *, nslot):
    """One query per grid step: compare both buckets, select the value."""
    q = q_ref[pl.program_id(0)]                        # scalar fingerprint
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, nslot), 1)
    first = jnp.minimum(_first_hit(fp1_ref[0], q, lane, nslot),
                        _first_hit(fp2_ref[0], q, lane + nslot, nslot))
    row = jax.lax.broadcasted_iota(jnp.int32, (nslot, 1), 0)
    out_ref[0] = (
        jnp.sum(jnp.where(row == first, val1_ref[0], 0), axis=0,
                keepdims=True)
        + jnp.sum(jnp.where(row + nslot == first, val2_ref[0], 0), axis=0,
                  keepdims=True))
    found_ref[0] = (first < 2 * nslot).astype(jnp.int32)


def race_lookup_pallas(fp_table, val_table, queries, bucket_idx,
                       *, interpret: bool | None = None):
    """fp_table: (NB, NSLOT) int32; val_table: (NB, NSLOT, VDIM);
    queries: (NQ,) int32 fingerprints; bucket_idx: (NQ, 2) int32.

    Returns (values (NQ, VDIM), found (NQ,) int32).
    """
    nb, nslot = fp_table.shape
    vdim = val_table.shape[-1]
    nq = queries.shape[0]
    if nq == 0:
        return (jnp.zeros((0, vdim), val_table.dtype),
                jnp.zeros((0,), jnp.int32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nq,),
        in_specs=[
            pl.BlockSpec((1, 1, nslot),
                         lambda i, bidx, q: (bidx[2 * i], 0, 0)),
            pl.BlockSpec((1, 1, nslot),
                         lambda i, bidx, q: (bidx[2 * i + 1], 0, 0)),
            pl.BlockSpec((1, nslot, vdim),
                         lambda i, bidx, q: (bidx[2 * i], 0, 0)),
            pl.BlockSpec((1, nslot, vdim),
                         lambda i, bidx, q: (bidx[2 * i + 1], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, vdim), lambda i, bidx, q: (i, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda i, bidx, q: (i, 0, 0)),
        ],
    )
    fp3 = fp_table.reshape(nb, 1, nslot)
    values, found = pl.pallas_call(
        functools.partial(_lookup_kernel, nslot=nslot),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nq, 1, vdim), val_table.dtype),
            jax.ShapeDtypeStruct((nq, 1, 1), jnp.int32),
        ],
        interpret=interpret_mode() if interpret is None else interpret,
    )(bucket_idx.reshape(2 * nq), queries, fp3, fp3, val_table, val_table)
    return values.reshape(nq, vdim), found.reshape(nq)


# --------------------------------------------------------- sharded table
def _gather_tile(rows_ref, base, fp_ref, val_ref, fp1_sc, fp2_sc, val_sc,
                 *, qblock, nslot):
    """Copy the tile's 2*QBLOCK candidate buckets out of the resident
    shard: bucket-1/bucket-2 fingerprint rows into ``fp1_sc``/``fp2_sc``
    (QBLOCK, NSLOT), and the value rows, per query contiguous (q0b0,
    q0b1, q1b0, ...), into ``val_sc`` (QBLOCK*2*NSLOT, VDIM). The bucket
    rows are read from SMEM at ``rows_ref[base + 2*i + b]``; the block's
    leading singleton shard axis is indexed away."""
    def body(i, carry):
        r1 = rows_ref[base + 2 * i]
        r2 = rows_ref[base + 2 * i + 1]
        fp1_sc[pl.ds(i, 1), :] = fp_ref[0, pl.ds(r1, 1), :]
        fp2_sc[pl.ds(i, 1), :] = fp_ref[0, pl.ds(r2, 1), :]
        off = pl.multiple_of(i * 2 * nslot, 2 * nslot)
        val_sc[pl.ds(off, nslot), :] = val_ref[0, r1]
        val_sc[pl.ds(off + nslot, nslot), :] = val_ref[0, r2]
        return carry

    jax.lax.fori_loop(0, qblock, body, 0)


def _tile_select(q, fp1, fp2, vals, *, qblock, nslot):
    """The sharded kernel's tile body, on one shard.

    Compare fingerprints across the whole tile (VPU), then select each
    query's first-hit value row with ONE flat one-hot contraction
    (QBLOCK, QBLOCK*2*NSLOT) @ (QBLOCK*2*NSLOT, VDIM) so the select runs
    on the MXU instead of per query.

    ``q`` (QBLOCK, 1) fingerprints, ``fp1``/``fp2`` (QBLOCK, NSLOT) the
    candidate buckets' fingerprints, ``vals`` the gathered value tile.
    Returns (out (QBLOCK, VDIM), found (QBLOCK, 1) bool).
    """
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, nslot), 1)
    first = jnp.minimum(_first_hit(fp1, q, lane, nslot),
                        _first_hit(fp2, q, lane + nslot, nslot))
    found = first < 2 * nslot                           # (QBLOCK, 1)
    # row (2*NSLOT)*i + s of the value tile is query i's s-th candidate
    sel = first + jax.lax.broadcasted_iota(
        jnp.int32, (qblock, 1), 0) * (2 * nslot)
    onehot = ((jax.lax.broadcasted_iota(
        jnp.int32, (qblock, 2 * qblock * nslot), 1) == sel)
        & found).astype(vals.dtype)
    out = jax.lax.dot(onehot, vals, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=vals.dtype)
    return out, found


def _tile_scratch(qblock, nslot, vdim, dtype):
    return [pltpu.VMEM((qblock, nslot), jnp.int32),
            pltpu.VMEM((qblock, nslot), jnp.int32),
            pltpu.VMEM((qblock * 2 * nslot, vdim), dtype)]


def _lookup_kernel_sharded(rows_ref, q_ref, fp_ref, val_ref, out_ref,
                           found_ref, fp1_sc, fp2_sc, val_sc, *, qblock,
                           nslot):
    """One (shard, tile) pair per grid step: the BlockSpec index map has
    already selected shard ``s``'s table, so the body gathers and selects
    within that one shard, its leading singleton shard axis squeezed
    off."""
    tile = pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)
    _gather_tile(rows_ref, tile * 2 * qblock, fp_ref, val_ref, fp1_sc,
                 fp2_sc, val_sc, qblock=qblock, nslot=nslot)
    out, found = _tile_select(q_ref[0], fp1_sc[...], fp2_sc[...],
                              val_sc[...], qblock=qblock, nslot=nslot)
    out_ref[0] = out
    found_ref[0] = found.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("qblock", "interpret"))
def sharded_lookup_call(fp_tables, val_tables, q_g, b_g, *, qblock: int,
                        interpret: bool | None = None):
    """The sharded kernel on already-grouped queries — a pure function of
    shapes (no host work), so it jits and lowers like any kernel.

    ``q_g`` (NS, QCAP) int32 per-shard fingerprints, ``b_g`` (NS, QCAP,
    2) int32 intra-shard bucket rows, QCAP a multiple of ``qblock``.
    Returns (values (NS, QCAP, VDIM), found (NS, QCAP) int32).

    Per-shard index map: grid = (NS, QCAP // QBLOCK) with the shard as
    the MAJOR dimension, and the table BlockSpecs select block ``(s, 0,
    0)`` — one shard's table resident per step (revisited across that
    shard's tiles, which are the minor/fast dimension). VMEM high-water
    is one shard's table + one query tile regardless of NS.
    """
    ns, nb, nslot = fp_tables.shape
    vdim = val_tables.shape[-1]
    qcap = q_g.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(ns, qcap // qblock),
        in_specs=[
            pl.BlockSpec((1, qblock, 1), lambda si, ti, rows: (si, ti, 0)),
            # per-shard index map: ONLY shard si's table this step
            pl.BlockSpec((1, nb, nslot), lambda si, ti, rows: (si, 0, 0)),
            pl.BlockSpec((1, nb, nslot, vdim),
                         lambda si, ti, rows: (si, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, qblock, vdim),
                         lambda si, ti, rows: (si, ti, 0)),
            pl.BlockSpec((1, qblock, 1), lambda si, ti, rows: (si, ti, 0)),
        ],
        scratch_shapes=_tile_scratch(qblock, nslot, vdim, val_tables.dtype),
    )
    values, found = pl.pallas_call(
        functools.partial(_lookup_kernel_sharded, qblock=qblock,
                          nslot=nslot),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((ns, qcap, vdim), val_tables.dtype),
            jax.ShapeDtypeStruct((ns, qcap, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret_mode() if interpret is None else interpret,
    )(b_g.reshape(ns * qcap * 2), q_g.reshape(ns, qcap, 1), fp_tables,
      val_tables)
    return values, found[..., 0]


def _group_slots(queries, bucket_idx, shard_idx, ns: int, qblock: int):
    """Group queries per shard with a stable sort (preserving intra-shard
    order) and pad each shard to QCAP, a multiple of the tile, with null
    queries (fingerprint 0 matches nothing). Returns (q_g (NS, QCAP), b_g
    (NS, QCAP, 2), inv (NQ,) int32, qblock): ``inv[i]`` is key ``i``'s
    flat slot ``shard * QCAP + within`` in the kernel's padded output.
    The pool over a mesh of memory nodes (``MeshPoolRaceTable``) groups
    its queries per node with it too, a node for a shard, to the pool
    kernel's tile."""
    q = np.asarray(queries, np.int32)
    b = np.asarray(bucket_idx, np.int32)
    s = np.asarray(shard_idx, np.int64)
    counts = np.bincount(s, minlength=ns)
    qblock = min(qblock, _rup(int(counts.max()), 8))
    qcap = _rup(int(counts.max()), qblock)
    order = np.argsort(s, kind="stable")
    ss = s[order]
    starts = np.concatenate(([0], np.cumsum(counts[:-1])))
    within = np.arange(len(q)) - starts[ss]
    q_g = np.zeros((ns, qcap), np.int32)
    b_g = np.zeros((ns, qcap, 2), np.int32)
    q_g[ss, within] = q[order]
    b_g[ss, within] = b[order]
    inv = np.empty(len(q), np.int32)
    inv[order] = ss * qcap + within
    return q_g, b_g, inv, qblock


def group_by_shard(queries, bucket_idx, shard_idx, ns: int, qblock: int):
    """Host-side prep of the sharded kernel: the queries grouped and
    padded per shard as :func:`sharded_lookup_call` takes them. Returns
    (q_g (NS, QCAP), b_g (NS, QCAP, 2), pos (NS, QCAP) original index per
    slot or -1, qblock)."""
    q_g, b_g, inv, qblock = _group_slots(queries, bucket_idx, shard_idx,
                                         ns, qblock)
    pos = np.full(q_g.shape, -1, np.int64)
    pos.reshape(-1)[inv] = np.arange(len(inv))
    return q_g, b_g, pos, qblock


@jax.jit
def in_key_order(values, found, inv):
    """The sharded kernel's padded answers, (NS, QCAP, VDIM) and (NS,
    QCAP), as (NQ, VDIM) and (NQ,) in the keys' order: a gather of the
    rows ``inv`` (:func:`_group_slots`). Rows move whole, so the answers
    stay bit-exact; padding slots are never read. The pool over a mesh of
    memory nodes orders its nodes' gathered answers with it too, inside
    its SPMD program (``ops.pool_mesh_lookup``)."""
    return values.reshape(-1, values.shape[-1])[inv], found.reshape(-1)[inv]


def race_lookup_pallas_sharded(fp_tables, val_tables, queries, bucket_idx,
                               shard_idx, *, qblock: int = 64,
                               interpret: bool | None = None):
    """Sharded multi-query lookup (the dkv shard-map kernel).

    ``fp_tables`` (NS, NB, NSLOT) int32; ``val_tables`` (NS, NB, NSLOT,
    VDIM); ``queries`` (NQ,) int32 fingerprints; ``bucket_idx`` (NQ, 2)
    int32 *intra-shard* bucket rows; ``shard_idx`` (NQ,) int32 owning
    shard per query. Returns device arrays (values (NQ, VDIM), found
    (NQ,) int32) in input order: the queries grouped per shard on the
    host, then on the device :func:`sharded_lookup_call` and
    :func:`in_key_order`, so the padded answers never leave the device.
    Not jit-wrapped — the grouping is data-dependent.

    Spans (:mod:`repro.obs`), in order: ``race.group`` (with the slots,
    the padding among them and QCAP), ``race.to_device`` (the grouped
    queries and their slots in the output, and the tables where they are
    passed as host arrays, until they are on the device; device arrays
    count no bytes) and ``race.kernel`` (dispatch of the kernel and of
    the gather into the keys' order; neither is waited for).
    """
    ns = fp_tables.shape[0]
    vdim = val_tables.shape[-1]
    nq = np.shape(queries)[0]
    if nq == 0:
        return (jnp.zeros((0, vdim), val_tables.dtype),
                jnp.zeros((0,), jnp.int32))
    with obs.span("race.group") as add:
        q_g, b_g, inv, qblock = _group_slots(queries, bucket_idx,
                                             shard_idx, ns, qblock)
        add(slots=q_g.size, padded_slots=q_g.size - nq, qcap=q_g.shape[1])
    operands = (fp_tables, val_tables, q_g, b_g, inv)
    with obs.span("race.to_device", h2d_bytes=sum(
            a.nbytes for a in operands if isinstance(a, np.ndarray))):
        *operands, inv = jax.block_until_ready(jax.device_put(operands))
    with obs.span("race.kernel", variant="sharded"):
        values, found = sharded_lookup_call(*operands, qblock=qblock,
                                            interpret=interpret)
        return in_key_order(values, found, inv)
