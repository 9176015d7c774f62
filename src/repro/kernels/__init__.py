"""Pallas TPU kernels for the compute hot-spots (each with ops.py jit
wrapper and ref.py pure-jnp oracle). Each kernel compiles for the TPU
through Mosaic; on a CPU backend it runs in Pallas interpret mode, which
:func:`interpret_mode` decides for every kernel in one place:

  race_lookup/      batched one-sided KV lookup over a RACE hash table in
                    device memory (the meta-server / DrTM-KV data path —
                    the TPU analogue of the paper's one-sided RDMA READ)
  serverless_stage/ chunk-granular payload scatter/gather: packs K ragged
                    function payloads into one contiguous MR slab (and
                    unpacks on the receiver) so a serverless chain hop
                    issues ceil(K/slab) doorbells instead of K
  flash_attention/  blockwise causal GQA attention w/ sliding window and
                    logit softcap (serving/training hot spot)
  rwkv6/            chunked data-dependent-decay WKV scan (rwkv6-7b)
"""

import jax


def interpret_mode() -> bool:
    """True exactly when JAX's default backend is the CPU: there the
    Pallas kernels run in interpret mode, and on an accelerator they run
    compiled. The RACE-lookup and stage kernels take ``interpret=None``
    to mean this; ``False`` lets a CPU host compile them for a described
    TPU (tests/test_tpu_compile.py)."""
    return jax.default_backend() == "cpu"
