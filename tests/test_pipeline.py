"""Pipeline parallelism: GPipe schedule == sequential reference (fwd and
grad), run on 4 host devices in a subprocess."""

import os
import subprocess
import sys

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys; sys.path.insert(0, %r)
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.distributed.pipeline import pipeline_apply, split_microbatches

S, M, MB, D = 4, 8, 2, 16
rng = np.random.RandomState(0)
params = jnp.asarray(rng.randn(S, D, D).astype(np.float32) * 0.3)
x = jnp.asarray(rng.randn(M * MB, D).astype(np.float32))


def stage_fn(w, h):
    return jax.nn.relu(h @ w)


def sequential(params, xb):
    h = xb
    for s in range(S):
        h = stage_fn(params[s], h)
    return h


mesh = Mesh(np.array(jax.devices()).reshape(S), ("stage",))
micro = split_microbatches(x, M)
out_pp = pipeline_apply(stage_fn, params, micro, mesh, axis="stage")
out_ref = sequential(params, x).reshape(M, MB, D)
np.testing.assert_allclose(np.asarray(out_pp), np.asarray(out_ref),
                           atol=1e-5)

# gradients flow through the schedule (GPipe backward for free)
def loss_pp(p):
    return jnp.sum(pipeline_apply(stage_fn, p, micro, mesh,
                                  axis="stage") ** 2)

def loss_ref(p):
    return jnp.sum(sequential(p, x) ** 2)

g_pp = jax.grad(loss_pp)(params)
g_ref = jax.grad(loss_ref)(params)
np.testing.assert_allclose(np.asarray(g_pp), np.asarray(g_ref),
                           rtol=2e-4, atol=2e-4)
print("PIPELINE_OK")
""" % (SRC,)


def test_pipeline_matches_sequential():
    # virtual host devices on purpose: the child never reaches for an
    # accelerator that this process may hold
    out = subprocess.run([sys.executable, "-c", CODE],
                         capture_output=True, text=True, timeout=600,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert "PIPELINE_OK" in out.stdout, out.stdout + out.stderr
