"""Batched serving driver: prefill + decode with slot-based continuous
batching. CPU-runnable with --smoke; production decode shapes are covered
by the dry-run.

Serving workers bootstrap through the elastic control plane: the step
executables come from an ExecutablePool, so a new worker joining a serving
fleet reuses the pool entry instead of recompiling (the paper's fast
control path; see examples/serverless_transfer.py for the latency story).
"""

from __future__ import annotations

import argparse
import time
from typing import List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_smoke_config
from repro.elastic import ExecutablePool
from repro.launch.cache import enable_compile_cache
from repro.launch.steps import make_decode_step
from repro.models import init_decode_cache, init_params, prefill


class ServingWorker:
    """One model replica with ``slots`` concurrent sequences."""

    def __init__(self, cfg, params, slots: int, max_len: int,
                 pool: Optional[ExecutablePool] = None):
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.pool = pool or ExecutablePool()
        self.bootstrap_s = None
        t0 = time.time()
        key = ("decode", cfg.name, slots, max_len)
        kind, fn = self.pool.get(key)
        if fn is None:
            fn = jax.jit(make_decode_step(cfg))
            # warm compile against representative shapes
            cache = init_decode_cache(cfg, slots, max_len, enc_len=16)
            fn(params, cache, jnp.zeros((slots,), jnp.int32),
               jnp.asarray(4))
            self.pool.put(key, fn)
        self.decode_fn = fn
        self.cache = init_decode_cache(cfg, slots, max_len, enc_len=16)
        self.cur_len = 4
        self.bootstrap_s = time.time() - t0

    def decode_tokens(self, tokens: np.ndarray, n_steps: int
                      ) -> np.ndarray:
        """Greedy continuation for all slots."""
        out = []
        toks = jnp.asarray(tokens, jnp.int32)
        for _ in range(n_steps):
            logits, self.cache = self.decode_fn(
                self.params, self.cache, toks, jnp.asarray(self.cur_len))
            toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            self.cur_len += 1
            out.append(np.asarray(toks))
        return np.stack(out, axis=1)           # (slots, n_steps)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--replicas", type=int, default=2)
    args = ap.parse_args()
    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    pool = ExecutablePool()
    for i in range(args.replicas):
        w = ServingWorker(cfg, params, args.slots, args.max_len, pool=pool)
        toks = w.decode_tokens(np.zeros(args.slots, np.int32), args.steps)
        print(f"replica {i}: bootstrap {w.bootstrap_s*1e3:8.2f} ms "
              f"({'pool hit' if i else 'cold compile'}), "
              f"decoded {toks.shape[1]} steps x {toks.shape[0]} slots")
    print(f"pool stats: hits={pool.stat_hits} misses={pool.stat_misses}")


if __name__ == "__main__":
    main()
