"""End-to-end integration: training convergence, crash-resume determinism,
elastic scale events, serving bootstrap via the executable pool."""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_training_loss_decreases(tmp_path):
    from repro.launch.train import run
    losses = run("qwen2_0_5b", smoke=True, steps=30, batch=8, seq=128,
                 ckpt_dir=None, lr=3e-3)
    assert losses[-1] < losses[0] - 0.2


def test_crash_resume_bit_exact(tmp_path):
    """Train 20 straight vs train 10 + restart + 10: identical params."""
    from repro.launch.train import run
    from repro.checkpoint import restore_checkpoint
    from repro.configs import get_smoke_config
    from repro.models import init_params
    from repro.optim import adamw_init

    d1 = str(tmp_path / "straight")
    d2 = str(tmp_path / "resumed")
    run("olmo_1b", smoke=True, steps=20, batch=4, seq=64, ckpt_dir=d1,
        ckpt_every=10)
    run("olmo_1b", smoke=True, steps=10, batch=4, seq=64, ckpt_dir=d2,
        ckpt_every=10)
    # "crash": new process state; resume picks up from step 10
    run("olmo_1b", smoke=True, steps=20, batch=4, seq=64, ckpt_dir=d2,
        ckpt_every=10)

    cfg = get_smoke_config("olmo_1b")
    template = (init_params(cfg, jax.random.PRNGKey(0)),
                adamw_init(init_params(cfg, jax.random.PRNGKey(0))))
    s1, (p1, _), _ = restore_checkpoint(d1, template)
    s2, (p2, _), _ = restore_checkpoint(d2, template)
    assert s1 == s2 == 20
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_elastic_trainer_multi_device_subprocess():
    """Scale 2->4->8 workers on 8 host devices; generic-pool bootstrap must
    be orders of magnitude faster than the cold compile."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, %r)
import jax, jax.numpy as jnp
from repro.configs import get_smoke_config
from repro.elastic import ElasticTrainer
from repro.launch.steps import make_train_step
from repro.models import init_params
from repro.optim import adamw_init
import numpy as np

cfg = get_smoke_config("qwen2_0_5b")

def make_step(mesh):
    inner = make_train_step(cfg, lr=1e-3)
    def step(state, batch):
        params, opt = state
        loss, params, opt = inner(params, opt, batch)
        return loss, (params, opt)
    return step

def init_state():
    p = init_params(cfg, jax.random.PRNGKey(0))
    return (p, adamw_init(p))

batch = {"tokens": np.zeros((8, 64), np.int32),
         "labels": np.ones((8, 64), np.int32)}
tr = ElasticTrainer(cfg, make_step, init_state, ladder=(2, 4, 8),
                    example_batch=batch)
tr.prewarm()
ev2 = tr.scale_to(2)
l0 = tr.train_step(batch)
ev4 = tr.scale_to(4)
l1 = tr.train_step(batch)
ev8 = tr.scale_to(8)
l2 = tr.train_step(batch)
assert ev2["kind"] == "generic" and ev4["kind"] == "generic"
assert ev8["kind"] == "generic"
# scale-up through the pool is fast (no compile on the critical path)
assert ev4["control_s"] < 1.0, ev4
cold = tr.scale_to(1)            # 1 not in ladder -> cold compile
assert cold["kind"] == "cold"
assert cold["control_s"] > ev4["control_s"]
print("ELASTIC_OK", ev4["control_s"], cold["control_s"])
""" % (os.path.abspath(SRC),)
    # virtual host devices on purpose: the child never reaches for an
    # accelerator that this process may hold
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=600,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert "ELASTIC_OK" in out.stdout, out.stdout + out.stderr


def test_serving_pool_bootstrap_speedup():
    from repro.configs import get_smoke_config
    from repro.elastic import ExecutablePool
    from repro.launch.serve import ServingWorker
    from repro.models import init_params

    cfg = get_smoke_config("olmo_1b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    pool = ExecutablePool()
    w1 = ServingWorker(cfg, params, slots=2, max_len=64, pool=pool)
    w2 = ServingWorker(cfg, params, slots=2, max_len=64, pool=pool)
    # worker 2 reuses the pooled executable: >=20x faster bootstrap
    assert w2.bootstrap_s < w1.bootstrap_s / 20.0
    toks = w2.decode_tokens(np.zeros(2, np.int32), 4)
    assert toks.shape == (2, 4)


def test_race_spike_bootstrap_krcore_vs_verbs():
    """Mini Fig-14: spawn workers under a load spike; KRCORE bootstrap is
    orders of magnitude faster than per-process Verbs control path."""
    from repro.core import make_cluster, VerbsProcess
    from repro.kvs import RaceKVStore
    from repro.kvs.race import RaceClient

    cluster = make_cluster(n_nodes=3, n_meta=1)
    env = cluster.env
    store = RaceKVStore(cluster.node("n2"), n_buckets=256)
    for k in range(1, 33):
        store.insert(k, b"val")

    N = 16

    def krcore_spike():
        t0 = env.now
        for i in range(N):
            yield env.timeout(cluster.modules["n0"].cm.fork_worker_us)
            client = RaceClient(cluster.module("n0"), store)
            yield from client.bootstrap()
            v = yield from client.lookup(1 + (i % 32))
            assert v == b"val"
        return env.now - t0

    kr_us = env.run_process(krcore_spike(), "kr")

    def verbs_spike():
        t0 = env.now
        for i in range(N):
            yield env.timeout(cluster.modules["n0"].cm.fork_worker_us)
            proc = VerbsProcess(cluster.node("n1"))
            yield from proc.connect(cluster.node("n2"))
        return env.now - t0

    vb_us = env.run_process(verbs_spike(), "vb")
    # paper: 1.4s -> 244ms is ~5.7x; with fork ~1.35ms/worker dominating
    # KRCORE, the ratio here must be >= 5x
    assert vb_us > 5 * kr_us, (vb_us, kr_us)
    # KRCORE is bottlenecked by worker creation, not networking (Fig 14)
    assert kr_us < N * 1.25 * cluster.modules["n0"].cm.fork_worker_us
