"""The paged-attention kernel compiles for a TPU v5e at llama3.2-1b widths.

Interpret mode never checks the Mosaic lowering rules (block-shape tiling,
VMEM limits), so these tests compile the kernel for a *described* v5e chip
with the TPU compiler installed alongside JAX — no chip attached. They cover
decode (C=1) and the chunk width the serving launcher uses.

The topology is described only inside the module-scoped fixture below, never
at import time: the TPU library may be loaded by one process at a time, and
every pytest worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels.paged_attention.kernel import paged_attention_pallas
from repro.launch.serve import DEFAULT_CHUNK

BLOCK_SIZE = 16
SLOTS = 8
MAX_BLOCKS_PER_SEQ = 34          # 544 tokens: a 512-token prompt + 32 new
NUM_BLOCKS = SLOTS * MAX_BLOCKS_PER_SEQ


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("chunk", [1, DEFAULT_CHUNK])
def test_paged_kernel_compiles_for_v5e_at_llama32_1b_widths(one_chip, chunk):
    a = get_config("llama3.2-1b").attention
    H, K, D = a.num_heads, a.num_kv_heads, a.head_dim
    assert (H, K, D) == (32, 8, 64)

    def shape(s, dtype):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    pool = shape((NUM_BLOCKS, BLOCK_SIZE, K, D), jnp.bfloat16)
    args = (shape((SLOTS, chunk, H, D), jnp.bfloat16), pool, pool,
            shape((SLOTS, MAX_BLOCKS_PER_SEQ), jnp.int32),
            shape((SLOTS,), jnp.int32), shape((SLOTS,), jnp.int32))
    fn = jax.jit(lambda *xs: paged_attention_pallas(
        *xs, block_size=BLOCK_SIZE, interpret=False))
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
