"""Pallas TPU kernel: blockwise causal GQA attention (flash-style).

Supports GQA (q-head -> kv-head group mapping via BlockSpec index maps),
sliding-window masking and gemma2-style attention-logit softcap. Online
softmax with fp32 accumulators held in VMEM scratch across the kv-block
grid dimension (the innermost, sequential one on TPU).

Block plan (per (batch*q_head, q_block) program family):
  q block   (1, 1, BQ, D)    VMEM
  k/v block (1, 1, BK, D)    VMEM (kv head = q head // group)
  acc       (BQ, D) f32      VMEM scratch, persists over the kv dimension
  m, l      (BQ, 128) f32    VMEM scratch (lane-padded row stats)

MXU alignment: BQ/BK multiples of 128, D = head_dim (padded by caller if
needed). Causal skipping is done with pl.when on whole blocks — skipped
blocks still occupy grid slots but do no FLOPs.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale, bq, bk, causal, window, cap, kv_len):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * bq
    k_start = ki * bk
    # whole-block skip condition (strictly above the causal diagonal /
    # entirely outside the sliding window): skipped blocks do no FLOPs.
    conds = []
    if causal:
        conds.append(k_start <= q_start + bq - 1)
    if window is not None:
        conds.append(k_start + bk - 1 > q_start - window)
    run = functools.reduce(jnp.logical_and, conds) if conds else None

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # (BQ, D)
        k = k_ref[0, 0].astype(jnp.float32)              # (BK, D)
        v = v_ref[0, 0]                                   # (BK, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (BQ, BK)
        if cap is not None:
            s = cap * jnp.tanh(s / cap)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        keep = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            keep &= kpos <= qpos
        if window is not None:
            keep &= kpos > qpos - window
        if kv_len is not None:
            keep &= kpos < kv_len
        s = jnp.where(keep, s, NEG_INF)

        m_prev = m_ref[:, 0]                              # (BQ,)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[:, 0] = l_ref[:, 0] * corr + jnp.sum(p, axis=1)
        m_ref[:, 0] = m_new
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if run is None:
        _compute()
    else:
        pl.when(run)(_compute)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal=True, window=None, cap=None,
                           bq=128, bk=128, kv_len=None):
    """q: (B, Hq, Sq, D); k/v: (B, Hkv, Skv, D) -> (B, Hq, Sq, D)."""
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    group = hq // hkv
    bq = min(bq, sq)
    bk = min(bk, skv)
    assert sq % bq == 0 and skv % bk == 0
    nq, nk = sq // bq, skv // bk
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(
        _flash_kernel, scale=scale, bq=bq, bk=bk, causal=causal,
        window=window, cap=cap, kv_len=kv_len)

    return pl.pallas_call(
        kernel,
        grid=(b * hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d),
                         lambda h, i, j: (h // hq, h % hq, i, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda h, i, j: (h // hq, (h % hq) // group, j, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda h, i, j: (h // hq, (h % hq) // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda h, i, j: (h // hq, h % hq, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        interpret=interpret_mode(),
    )(q, k, v)
