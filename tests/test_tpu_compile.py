"""Compile-only checks of the main path's Pallas kernel for a described
TPU v5e (nothing runs; the TPU compiler is installed, the chip is not).

The paged decode kernel is compiled at the cluster nodes' real head
widths with the serving engine's pool layout: float32 pool
(``ServeEngine._paged_fresh_cache``), 16-token blocks, and the default
pool of a batch-4, max_len-192 node (48 blocks, 12 per row); and at the
shape the benchmark's nodes serve (max_len 1,120: 70 blocks a row, an
``nb_cap`` of 72) for MHA and for the GQA widths no cell runs yet.  A
kernel the TPU compiler refuses (unaligned tiling, a chunk too large
for VMEM) fails here, where interpret-mode tests pass.

The topology is described only inside the fixture: loading the TPU
library at import or collection time would make pytest-xdist workers
collect different tests.  Keep every such test in this one file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention import paged_decode_attention_pallas

B, BLOCK, NB = 4, 16, 12           # engine rows, block size, blocks per row


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("q_dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("heads,kv_heads,head_dim", [
    (16, 16, 128),                 # olmo-1b
    (25, 5, 64),                   # hymba-1.5b global attention layers
], ids=["olmo-1b", "hymba-1.5b-global"])
def test_paged_decode_kernel_compiles_for_v5e(one_chip, heads, kv_heads,
                                              head_dim, q_dtype):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((B * NB, BLOCK, kv_heads, head_dim), jnp.float32)
    args = (sds((B, heads, head_dim), q_dtype), pool, pool,
            sds((B, NB), jnp.int32), sds((B,), jnp.int32),
            sds((B,), jnp.int32))
    fn = jax.jit(functools.partial(paged_decode_attention_pallas,
                                   interpret=False))
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("heads,kv_heads,head_dim,softcap", [
    (16, 16, 128, None),           # olmo-1b
    (32, 8, 64, None),             # GQA 32/8 x 64 (granite-4.0-h-micro)
    (16, 8, 256, 50.0),            # gemma2's global layers, soft-capped
    (25, 5, 64, None),             # hymba-1.5b global attention layers
], ids=["olmo-1b", "gqa-32-8x64", "gemma2-softcap", "hymba-1.5b-global"])
def test_paged_decode_kernel_compiles_at_serving_shape(
        one_chip, heads, kv_heads, head_dim, softcap):
    rows, nb_cap = 70, 72          # max_len 1,120; _cont_nb_cap's bucket

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((B * rows, BLOCK, kv_heads, head_dim), jnp.float32)
    args = (sds((B, heads, head_dim), jnp.bfloat16), pool, pool,
            sds((B, nb_cap), jnp.int32), sds((B,), jnp.int32),
            sds((B,), jnp.int32))
    fn = jax.jit(functools.partial(paged_decode_attention_pallas,
                                   softcap=softcap, interpret=False))
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
