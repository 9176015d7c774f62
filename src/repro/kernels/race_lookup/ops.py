"""jit'd public wrappers for the RACE-lookup kernels."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .race_lookup import (in_key_order, race_lookup_pallas,
                          race_lookup_pallas_sharded)
from .pool import LANES, pool_lookup_pallas
from .ref import pool_lookup_ref, race_lookup_ref


@functools.partial(jax.jit, static_argnames=("impl",))
def race_lookup(fp_table, val_table, queries, bucket_idx,
                impl: str = "pallas"):
    """Batched two-choice hash lookup over the flat table
    (``DeviceRaceTable``).

    fp_table (NB, NSLOT) i32, val_table (NB, NSLOT, VDIM), queries (NQ,)
    i32 fingerprints, bucket_idx (NQ, 2) i32 -> (values (NQ, VDIM),
    found (NQ,) i32). The kernel compiles for the TPU and runs in
    interpret mode on a CPU backend (:func:`repro.kernels.interpret_mode`).
    ``impl``: ``"pallas"`` (the kernel: one query per grid step, its two
    candidate buckets fetched from HBM, no bound on the table's size) or
    ``"ref"`` (the pure-jnp oracle).
    """
    if impl == "ref":
        return race_lookup_ref(fp_table, val_table, queries, bucket_idx)
    if impl == "pallas":
        return race_lookup_pallas(fp_table, val_table, queries, bucket_idx)
    raise ValueError(f"unknown impl {impl!r}")


def pack_queries(qkeys, qfps, bucket_idx, inv=None) -> np.ndarray:
    """The pool layout's query operands as one int32 array, for one
    host-to-device put: ``(..., R, L)``, the queries along the last
    (lane) axis, zero-padded to ``L``, the next multiple of 128 lanes (a
    minor axis of 128s needs no relayout into the device's tiles, as a
    minor axis of 2 does).

    Rows: ``qkeys``, ``qfps``, the first and the second candidate bucket
    of ``bucket_idx``, each query in the same lane: :func:`pool_lookup`'s
    operands. qkeys and qfps are (..., Q) i32, bucket_idx (..., Q, 2).
    Given ``inv`` (NQ,) i32, queries grouped per node (a leading axis of
    N nodes) take a fifth row: node ``i``'s chunk of ``inv``, keys ``i *
    C`` up to ``(i + 1) * C`` with ``C = ceil(NQ / N)``, zero-padded
    (:func:`pool_mesh_lookup`'s operands). Sharded along its node axis,
    the array is one device buffer per device, however many the mesh
    has."""
    rows = [qkeys, qfps, bucket_idx[..., 0], bucket_idx[..., 1]]
    if inv is not None:
        n = np.shape(qkeys)[0]
        chunk = -(-len(inv) // n)
        rows.append(np.pad(inv, (0, n * chunk - len(inv))).reshape(n, chunk))
    lanes = -(-max(np.shape(r)[-1] for r in rows) // LANES) * LANES
    out = np.zeros((*np.shape(qkeys)[:-1], len(rows), lanes), np.int32)
    for i, row in enumerate(rows):
        out[..., i, :np.shape(row)[-1]] = row
    return out


@functools.partial(jax.jit, static_argnames=("nq", "nslot", "impl"))
def pool_lookup(index, keys, pool, queries, *, nq: int, nslot: int,
                impl: str = "pallas"):
    """RACE's two-level lookup over the pool layout (``PoolRaceTable``):
    both candidate buckets from the index, the KV block of each
    fingerprint match, the record whose stored key is the query key.

    index (ceil(NB * 2 * NSLOT / 128), 1, 128) i32, keys (ceil(CAP /
    128), 1, 128) i32, pool (CAP, 1, VDIM); queries (4, L) i32, the NQ
    queries packed as :func:`pack_queries` packs them (query keys, their
    8-bit fingerprints, first and second candidate bucket, in lanes 0 to
    NQ - 1), unpacked here on the device -> (values (NQ, VDIM), found
    (NQ,) i32, the KV blocks fetched over the batch, an i32 scalar).
    ``impl``: ``"pallas"`` (the kernel, index and pool left in HBM) or
    ``"ref"`` (the pure-jnp oracle)."""
    qkeys, qfps = queries[0, :nq], queries[1, :nq]
    bucket_idx = jnp.stack([queries[2, :nq], queries[3, :nq]], axis=1)
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


#: the mesh axis along which a pool's memory nodes are laid out
NODE_AXIS = "node"


@functools.partial(jax.jit,
                   static_argnames=("nq", "qcap", "mesh", "nslot", "impl"))
def pool_mesh_lookup(index, keys, pool, queries, *, nq: int, qcap: int,
                     mesh, nslot: int, impl: str = "pallas"):
    """RACE's two-level lookup over a pool of N memory nodes held on the
    devices of ``mesh`` (``MeshPoolRaceTable``): one SPMD program.

    index (N, ceil(NB * 2 * NSLOT / 128), 1, 128) i32, keys (N, ceil(CAP
    / 128), 1, 128) i32 and pool (N, CAP, 1, VDIM), each node's tables
    as :func:`pool_lookup` reads them; queries (N, 5, L) i32, the NQ
    keys' queries grouped and padded to QCAP per node (null queries have
    fingerprint 0) and packed by :func:`pack_queries`: per node the four
    rows :func:`pool_lookup` takes, then the node's chunk of ``inv``,
    each key's flat slot ``node * QCAP + within`` in the padded answers.
    All of these are sharded along their node axis over the mesh axis
    ``NODE_AXIS``, so each device holds N / D whole nodes and the
    queries cross to the devices as one buffer a device.

    Each device runs :func:`pool_lookup` for each of its nodes on that
    node's tables and rows, an all-gather over the mesh brings every
    node's chunk of ``inv`` and every node's padded answers to every
    device, and :func:`in_key_order` puts the answers in the keys' order
    there. Returns (values (NQ, VDIM), found (NQ,) i32, the KV blocks
    fetched over all nodes, an i32 scalar), each replicated over the
    mesh: the padded answers never leave the devices. ``impl`` is
    :func:`pool_lookup`'s, per node."""
    chunk = -(-nq // index.shape[0])

    def per_device(index, keys, pool, queries):
        values, found, blocks = [], [], 0
        for i in range(index.shape[0]):
            v, f, b = pool_lookup(index[i], keys[i], pool[i],
                                  queries[i, :4], nq=qcap, nslot=nslot,
                                  impl=impl)
            values.append(v)
            found.append(f)
            blocks = blocks + b
        values, found, inv = (
            jax.lax.all_gather(a, NODE_AXIS, tiled=True)
            for a in (jnp.stack(values), jnp.stack(found),
                      queries[:, 4, :chunk]))
        values, found = in_key_order(values, found,
                                     inv.reshape(-1)[:nq])
        return values, found, jax.lax.psum(blocks, NODE_AXIS)

    # the kernel's outputs carry no varying-axes type: the all-gather and
    # the psum make every output the same on every device
    return jax.shard_map(per_device, mesh=mesh, in_specs=(P(NODE_AXIS),) * 4,
                         out_specs=(P(),) * 3, check_vma=False)(
                             index, keys, pool, queries)


def race_lookup_sharded(fp_tables, val_tables, queries, bucket_idx,
                        shard_idx, impl: str = "pallas", qblock: int = 64):
    """Batched lookup over a SHARDED table set (the dkv shard map).

    fp_tables (NS, NB, NSLOT) i32, val_tables (NS, NB, NSLOT, VDIM),
    queries (NQ,) i32 fingerprints, bucket_idx (NQ, 2) i32 intra-shard
    rows, shard_idx (NQ,) i32 -> (values (NQ, VDIM), found (NQ,) i32).

    ``impl``: ``"pallas"`` (the sharded kernel: a grid dimension over
    shards with a per-shard index map, ONE shard's table VMEM-resident per
    step) or ``"ref"`` (the pure-jnp oracle, shard by shard).

    Not jit-wrapped: the per-shard grouping is data-dependent (the
    kernel and the gather back into the keys' order are jitted inside).
    """
    if impl == "pallas":
        return race_lookup_pallas_sharded(fp_tables, val_tables, queries,
                                          bucket_idx, shard_idx,
                                          qblock=qblock)
    if impl != "ref":
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
        v, f = race_lookup_ref(fp_tables[sid], val_tables[sid],
                               jnp.asarray(q[m]), jnp.asarray(b[m]))
        out_v[m] = np.asarray(v)
        out_f[m] = np.asarray(f)
    return jnp.asarray(out_v), jnp.asarray(out_f)
