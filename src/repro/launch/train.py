"""End-to-end training driver (CPU-runnable with --smoke; production
configs are exercised via the dry-run).

    PYTHONPATH=src python -m repro.launch.train --arch qwen2_0_5b --smoke \
        --steps 100 --batch 8 --seq 128 --ckpt-dir /tmp/ck

Features: synthetic-data pipeline with prefetch, AdamW + clipping, async
sharded checkpoints with crash-safe auto-resume, per-step logging.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, get_smoke_config
from repro.data import SyntheticLM, make_batch_iterator
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_train_step
from repro.models import init_params
from repro.optim import adamw_init


def run(arch: str, smoke: bool, steps: int, batch: int, seq: int,
        ckpt_dir: str | None, ckpt_every: int = 20, lr: float = 3e-3,
        log_every: int = 10, seed: int = 0):
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    if cfg.grad_accum > 1 and batch % cfg.grad_accum:
        cfg = dataclasses.replace(cfg, grad_accum=1)
    mesh = make_host_mesh()
    params = init_params(cfg, jax.random.PRNGKey(seed))
    opt_state = adamw_init(params)
    start_step = 0
    manager = CheckpointManager(ckpt_dir) if ckpt_dir else None
    if manager is not None:
        restored = manager.restore_latest((params, opt_state))
        if restored is not None:
            start_step, (params, opt_state), meta = restored
            print(f"resumed from step {start_step}")

    data = SyntheticLM(cfg.vocab, seq, batch, seed=seed)
    data.seek(start_step)
    step_fn = jax.jit(make_train_step(cfg, lr=lr))

    it = make_batch_iterator(data, mesh=mesh)
    losses = []
    t0 = time.time()
    for step in range(start_step, steps):
        batch_dev = next(it)
        loss, params, opt_state = step_fn(params, opt_state, batch_dev)
        losses.append(float(loss))
        if (step + 1) % log_every == 0:
            dt = (time.time() - t0) / log_every
            print(f"step {step+1:5d} loss {float(loss):.4f} "
                  f"{dt*1e3:.1f} ms/step", flush=True)
            t0 = time.time()
        if manager is not None and (step + 1) % ckpt_every == 0:
            manager.save_async(step + 1, (params, opt_state),
                               {"loss": float(loss)})
    if manager is not None:
        manager.wait()
        manager.save_async(steps, (params, opt_state),
                           {"loss": losses[-1] if losses else None})
        manager.wait()
    return losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--lr", type=float, default=3e-3)
    args = ap.parse_args()
    enable_compile_cache()
    losses = run(args.arch, args.smoke, args.steps, args.batch, args.seq,
                 args.ckpt_dir, lr=args.lr)
    print(f"first loss {losses[0]:.4f} -> last loss {losses[-1]:.4f}")


if __name__ == "__main__":
    main()
