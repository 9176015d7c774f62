"""GPipe-style pipeline parallelism over a mesh axis (DESIGN.md §5 PP).

``pipeline_apply`` runs S stages (one per device along ``axis``) over M
microbatches with the classic (M + S - 1)-step schedule: stage s works on
microbatch t-s at step t; activations hop stage->stage+1 through
``jax.lax.ppermute``. Everything is differentiable (ppermute has a
transpose rule), so wrapping the whole thing in ``jax.grad`` yields the
standard GPipe backward schedule for free.

Intended use: the "pod" axis of the production mesh as the PP dimension
(layers split across pods, DCN hops amortized over microbatches), with
DP/TP inside each pod. Exercised in tests/test_pipeline.py on a host mesh.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
                   stage_params: Any, x: jnp.ndarray, mesh: Mesh,
                   axis: str = "stage"):
    """Run microbatches through a device pipeline.

    stage_fn:     (params_one_stage, activations (mb, ...)) -> same shape
    stage_params: pytree with leading dim S (one slice per stage)
    x:            (M, mb, ...) microbatches
    Returns (M, mb, ...) outputs (as produced by the LAST stage).
    """
    s_stages = mesh.shape[axis]
    m = x.shape[0]
    n_steps = m + s_stages - 1

    def per_stage(params_local, x_local):
        # params_local: (1, ...) this stage's slice; x_local: (M, mb, ...)
        # (inputs replicated; only stage 0 consumes them)
        params0 = jax.tree_util.tree_map(lambda t: t[0], params_local)
        stage_id = jax.lax.axis_index(axis)
        mb_shape = x_local.shape[1:]
        carry = jnp.zeros(mb_shape, x_local.dtype)    # incoming activation
        out_buf = jnp.zeros_like(x_local)             # (M, mb, ...)

        def step(t, state):
            carry, out_buf = state
            # stage 0 injects microbatch t (when valid); others use carry
            feed_idx = jnp.clip(t, 0, m - 1)
            inject = x_local[feed_idx]
            inp = jnp.where(stage_id == 0, inject, carry)
            out = stage_fn(params0, inp)
            # last stage records microbatch t - (S-1) when it is valid
            mb_idx = t - (s_stages - 1)
            is_last = stage_id == s_stages - 1
            valid = jnp.logical_and(is_last, mb_idx >= 0)
            write_idx = jnp.clip(mb_idx, 0, m - 1)
            cur = jax.lax.dynamic_index_in_dim(out_buf, write_idx, 0,
                                               keepdims=False)
            new = jnp.where(valid, out, cur)
            out_buf = jax.lax.dynamic_update_index_in_dim(
                out_buf, new, write_idx, 0)
            # ship activations one stage forward (ring; last->0 ignored)
            carry = jax.lax.ppermute(
                out, axis,
                [(i, (i + 1) % s_stages) for i in range(s_stages)])
            return carry, out_buf

        carry, out_buf = jax.lax.fori_loop(0, n_steps, step,
                                           (carry, out_buf))
        # only the last stage wrote anything; psum replicates its buffer
        # (all other stages contribute zeros)
        return jax.lax.psum(out_buf, axis)

    fn = jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(P(axis), P()),           # params split by stage
        out_specs=P(),                     # outputs replicated
        check_vma=False)
    return fn(stage_params, x)


def split_microbatches(batch: jnp.ndarray, n_micro: int) -> jnp.ndarray:
    """(B, ...) -> (M, B//M, ...)."""
    b = batch.shape[0]
    assert b % n_micro == 0, (b, n_micro)
    return batch.reshape(n_micro, b // n_micro, *batch.shape[1:])
