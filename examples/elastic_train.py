"""Elastic data-parallel training with a KRCORE-style control plane.

    python examples/elastic_train.py

It uses every device JAX sees: the ladder is the powers of two up to
``len(jax.devices())``. On a CPU host, run it with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` for eight virtual
devices.

The trainer pre-compiles a ladder of mesh sizes at boot (the statically-
initialized DCQPs of the paper); scale events then hit the executable pool
and complete in milliseconds, while an off-ladder size pays the cold
compile (the Verbs-analogue path). Loss keeps decreasing across resizes.
"""

import os
import sys
sys.path.insert(0, "src")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import jax

from repro.configs import get_smoke_config
from repro.data import SyntheticLM
from repro.elastic import ElasticTrainer
from repro.launch.steps import make_train_step
from repro.models import init_params
from repro.optim import adamw_init

cfg = get_smoke_config("qwen2_0_5b")


def make_step(mesh):
    inner = make_train_step(cfg, lr=3e-3)

    def step(state, batch):
        params, opt = state
        loss, params, opt = inner(params, opt, batch)
        return loss, (params, opt)
    return step


def init_state():
    p = init_params(cfg, jax.random.PRNGKey(0))
    return (p, adamw_init(p))


batch0 = {"tokens": np.zeros((8, 64), np.int32),
          "labels": np.zeros((8, 64), np.int32)}
n_dev = min(len(jax.devices()), 8)       # the batch of 8 splits evenly
ladder = tuple(2 ** i for i in range(n_dev.bit_length()))
tr = ElasticTrainer(cfg, make_step, init_state, ladder=ladder,
                    example_batch=batch0)
print(f"prewarming executable ladder {ladder} workers...")
tr.prewarm()

data = SyntheticLM(cfg.vocab, 64, 8, seed=1)
plan = [(n, 5) for n in ladder] + [(ladder[-2 if len(ladder) > 1 else 0], 5)]
for n, steps in plan:
    ev = tr.scale_to(n)
    print(f"scale -> {n} workers: {ev['kind']:>11s} path, "
          f"control {ev['control_s']*1e3:8.2f} ms")
    for _ in range(steps):
        loss = tr.train_step(next(data))
    print(f"   ... trained {steps} steps, loss {float(loss):.4f}")
