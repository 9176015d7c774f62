"""The main path's Pallas kernels compile for a TPU v5e (Mosaic), at the
widths chip_smoke.py drives them with: RACE lookup at vdim 256 (scalar at
the flat table's size, tiled at one shard's size, sharded at the store's
shard geometry) and the serverless stage gather at chunk 128.

Nothing runs: the chip is described, not attached, so these compile
from shapes alone and check that Mosaic accepts each kernel."""

import functools
import os
import sys

import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke as cs  # noqa: E402
from repro.kernels.race_lookup.race_lookup import (  # noqa: E402
    race_lookup_pallas, race_lookup_pallas_tiled, sharded_lookup_call)
from repro.kernels.serverless_stage.stage import (  # noqa: E402
    CHUNK, chunk_gather_pallas)

NQ = cs.BATCH
SLAB_CHUNKS = 16 * (1024 // 4 // CHUNK)      # one chain slab of 16 x 1 KB


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape_on_chip(topo):
    """ShapeDtypeStruct factory placed on the described chip; the
    persistent compilation cache is off meanwhile (an entry written for
    a described chip cannot be read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype=jnp.int32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _scalar(s):
    nb = cs.FLAT_BUCKETS
    return jax.jit(functools.partial(race_lookup_pallas, interpret=False)
                   ).lower(s((nb, cs.NSLOT)),
                           s((nb, cs.NSLOT, cs.VDIM), jnp.float32),
                           s((NQ,)), s((NQ, 2)))


def _tiled(s):
    nb = cs.SHARD_BUCKETS
    return jax.jit(functools.partial(race_lookup_pallas_tiled,
                                     interpret=False)
                   ).lower(s((nb, cs.NSLOT)),
                           s((nb, cs.NSLOT, cs.VDIM), jnp.float32),
                           s((NQ,)), s((NQ, 2)))


def _sharded(s):
    ns, nb, qcap = cs.N_SHARDS, cs.SHARD_BUCKETS, 64
    return sharded_lookup_call.lower(
        s((ns, nb, cs.NSLOT)), s((ns, nb, cs.NSLOT, cs.VDIM), jnp.float32),
        s((ns, qcap)), s((ns, qcap, 2)), qblock=64, interpret=False)


def _stage(s):
    return jax.jit(functools.partial(chunk_gather_pallas, chunk=CHUNK,
                                     interpret=False)
                   ).lower(s((SLAB_CHUNKS, CHUNK)), s((SLAB_CHUNKS,)),
                           s((SLAB_CHUNKS,)))


@pytest.mark.parametrize("lower", [_scalar, _tiled, _sharded, _stage],
                         ids=["race_scalar", "race_tiled", "race_sharded",
                              "stage_gather"])
def test_kernel_compiles_for_v5e(shape_on_chip, lower):
    compiled = lower(shape_on_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()
