"""jit'd public wrapper for the flash-attention kernel."""

from __future__ import annotations

import functools

import jax

from .flash_attention import flash_attention_pallas
from .ref import flash_attention_ref


@functools.partial(jax.jit, static_argnames=("causal", "window", "cap",
                                             "bq", "bk", "impl"))
def flash_attention(q, k, v, *, causal=True, window=None, cap=None,
                    bq=128, bk=128, impl: str = "pallas"):
    if impl == "ref":
        return flash_attention_ref(q, k, v, causal=causal, window=window,
                                   cap=cap)
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  cap=cap, bq=bq, bk=bk)
