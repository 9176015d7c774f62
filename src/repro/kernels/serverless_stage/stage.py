"""Pallas TPU kernel: payload staging (scatter/gather) for the serverless
chain's batched two-sided hops.

A chained-function hop must move K variable-length payloads from node A to
node B. Issuing one SEND per payload costs K doorbells; the serverless
subsystem instead *packs* the K payloads into a contiguous MR slab on the
sender (one doorbell per slab) and *unpacks* on the receiver. Both
directions are the same data movement — a chunk-granular gather with a
ragged tail mask — so ONE kernel serves both, driven by host-precomputed
routing tables (see :mod:`.ops` for the planners):

    pack:    slab_chunk[j]    <- payload_chunk[src_row[j]]   (j over slab)
    unpack:  payload_chunk[j] <- slab_chunk[src_row[j]]      (j over rows)

``src_row`` and ``valid`` ride the scalar-prefetch lane, so each grid step
DMAs exactly one CHUNK-wide block (the same discipline as the scalar
race-lookup kernel's per-bucket BlockSpecs), masks the ragged tail on the
VPU, and writes one output chunk. CHUNK defaults to 128 int32 lanes
(= 512 B), the TPU lane width, so every copy is a full-lane vector op.
Source and output are viewed as ``(N, 1, chunk)`` so that a one-chunk
block's last two dims equal the array's, as the TPU tiling requires.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

CHUNK = 128                     # int32 elements per staged chunk (512 B)


def _gather_kernel(src_row_ref, valid_ref, src_ref, out_ref, *, chunk):
    """One output chunk per grid step: copy the routed source chunk and
    zero the lanes beyond this chunk's valid length (ragged tail /
    routing hole)."""
    j = pl.program_id(0)
    v = valid_ref[j]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, chunk), 2)
    out_ref[...] = jnp.where(lane < v, src_ref[...], 0)


def chunk_gather_pallas(src, src_row, valid, *, chunk: int = CHUNK,
                        interpret: bool | None = None):
    """Gather ``len(src_row)`` chunks out of ``src``.

    src: (NSRC, chunk) int32 — chunk-granular view of the source buffer;
    src_row: (NOUT,) int32 — source chunk index per output chunk (rows
    with ``valid == 0`` may point anywhere in range — they produce
    zeros); valid: (NOUT,) int32 — number of live lanes per output chunk.

    Returns (NOUT, chunk) int32.
    """
    nout = src_row.shape[0]
    if nout == 0:
        return jnp.zeros((0, chunk), jnp.int32)
    nsrc = src.shape[0]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nout,),
        in_specs=[
            pl.BlockSpec((1, 1, chunk),
                         lambda j, rows, valid: (rows[j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk),
                               lambda j, rows, valid: (j, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_gather_kernel, chunk=chunk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nout, 1, chunk), jnp.int32),
        interpret=interpret_mode() if interpret is None else interpret,
    )(src_row, valid, src.reshape(nsrc, 1, chunk))
    return out.reshape(nout, chunk)
