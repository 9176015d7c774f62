"""jit'd public wrapper for the WKV kernel."""

from __future__ import annotations

import functools

import jax

from .ref import wkv_ref
from .rwkv6 import wkv_pallas


@functools.partial(jax.jit, static_argnames=("chunk", "impl"))
def wkv(r, k, v, logw, u, *, chunk: int = 16, impl: str = "pallas"):
    """RWKV-6 WKV scan: r,k,logw (B,H,S,dk); v (B,H,S,dv); u (H,dk)."""
    if impl == "ref":
        return wkv_ref(r, k, v, logw, u)
    return wkv_pallas(r, k, v, logw, u, chunk=chunk)
