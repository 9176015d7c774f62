"""Pallas TPU kernel: chunked RWKV-6 WKV scan with data-dependent decay.

Grid: (B*H, n_chunks) — the chunk dimension is innermost, so the fp32
state matrix (dk, dv) lives in VMEM scratch and persists across chunk
steps of the same (batch, head) program family (the standard TPU
sequential-grid carry trick).

Per chunk of length C (see models/rwkv6.py for the math):
    L   = inclusive cumulative log-decay           (C, dk)
    o   = (r * e^{L-logw}) @ S                      inter-chunk  (MXU)
        + tril((r*e^{L-logw}) @ (k*e^{-L})^T, -1) @ v  intra     (MXU)
        + (r . u*k) v                               bonus
    S   = e^{L_C} * S + (k * e^{L_C - L})^T @ v

Chunk size is 16 to keep |L| <= 4.25*16 well inside fp32 exp range
(the decay is clamped to [-4.25, -1e-6] by the model; see models/rwkv6.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, state_ref, *,
                chunk):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    r = r_ref[0].astype(jnp.float32)          # (C, dk)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)          # (C, dv)
    lw = lw_ref[0].astype(jnp.float32)        # (C, dk)
    u = u_ref[0].astype(jnp.float32)          # (1, dk) row

    S = state_ref[...]                        # (dk, dv) f32
    Lx = jnp.cumsum(lw, axis=0)               # inclusive
    Lex = Lx - lw                             # exclusive
    r_dec = r * jnp.exp(Lex)
    k_inc = k * jnp.exp(-Lx)

    c = r.shape[0]
    tri = (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
           > jax.lax.broadcasted_iota(jnp.int32, (c, c), 1))
    att = jax.lax.dot_general(r_dec, k_inc, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    att = jnp.where(tri, att, 0.0)
    o = jax.lax.dot_general(r_dec, S, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o = o + jax.lax.dot_general(att, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    # current-token bonus: (r_t . (u*k_t)) is a per-row scalar scaling v_t
    bonus = jnp.sum(r * u * k, axis=1, keepdims=True)          # (C, 1)
    o = o + bonus * v
    o_ref[0] = o.astype(o_ref.dtype)

    Ltot = Lx[-1:, :]                                          # (1, dk)
    carry = k * jnp.exp(Ltot - Lx)
    state_ref[...] = S * jnp.exp(Ltot).T + jax.lax.dot_general(
        carry, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def wkv_pallas(r, k, v, logw, u, *, chunk: int = 16):
    """r,k,logw: (B,H,S,dk); v: (B,H,S,dv); u: (H,dk).

    Returns o: (B,H,S,dv). State starts at zero (prefill semantics); the
    jnp reference (models/rwkv6.py::wkv_chunked) is the oracle.
    """
    b, h, s, dk = r.shape
    dv = v.shape[-1]
    c = min(chunk, s)
    assert s % c == 0
    nc = s // c

    def flat(t, dlast):
        return t.reshape(b * h, s, dlast)

    kernel = functools.partial(_wkv_kernel, chunk=c)
    o = pl.pallas_call(
        kernel,
        grid=(b * h, nc),
        in_specs=[
            pl.BlockSpec((1, c, dk), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, c, dk), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, c, dv), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, c, dk), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, dk), lambda i, j: (i % h, 0)),
        ],
        out_specs=pl.BlockSpec((1, c, dv), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, dv), r.dtype),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=interpret_mode(),
    )(flat(r, dk), flat(k, dk), flat(v, dv), flat(logw, dk), u)
    return o.reshape(b, h, s, dv)
