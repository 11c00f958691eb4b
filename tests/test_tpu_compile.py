"""The paged-attention kernel compiles for a TPU v5e at llama3.2-1b widths.

Interpret mode never checks the Mosaic lowering rules (block-shape tiling,
VMEM limits), so these tests compile the kernel for a *described* v5e chip
with the TPU compiler installed alongside JAX — no chip attached. They cover
decode (C=1) and the chunk width the serving launcher uses; the latent
kernel's block-table DMAs compile at DeepSeek-V2-Lite's decode-4k shapes.
The paged step itself compiles at the benchmark's widths (fewer layers, a
smaller pool), to pin which pools the TPU compiler lets the step write in
place.

The topology is described only inside the module-scoped fixture below, never
at import time: the TPU library may be loaded by one process at a time, and
every pytest worker imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs.base import (SHAPES, AttentionConfig, ModelConfig,
                                RunConfig, ShardingConfig)
from repro.configs.registry import get_config
from repro.kernels.paged_attention import ops
from repro.kernels.paged_attention.kernel import paged_attention_pallas
from repro.launch.serve import DEFAULT_CHUNK
from repro.runtime.steps import make_paged_serve_step

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


def test_latent_kernel_compiles_for_v5e_at_decode_4k_shapes(one_chip,
                                                            monkeypatch):
    """The latent kernel alone at DeepSeek-V2-Lite's decode-4k shapes: 24
    rows of 8 columns, 16 heads, 640 lanes, 288 table entries over 6,912
    pool blocks in bf16. Its block-table DMAs and double buffer lower
    through Mosaic and fit VMEM: one Mosaic call, with no temporaries."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    B, C, H, lanes, M = 24, DEFAULT_CHUNK, 16, 640, 288
    assert C == 8

    def shape(s, dtype):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    args = (shape((B, C, H, lanes), jnp.bfloat16),
            shape((B * M, BLOCK_SIZE, lanes), jnp.bfloat16),
            shape((B, M), jnp.int32), shape((B,), jnp.int32),
            shape((B,), jnp.int32))
    compiled = jax.jit(lambda *xs: ops.paged_latent_attention(
        *xs, block_size=BLOCK_SIZE, v_lanes=512, scale=0.1147)
    ).lower(*args).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1 and "paged_latent_attention" in calls[0]
    assert compiled.memory_analysis().temp_size_in_bytes == 0


STEP_LAYERS, STEP_BLOCKS = 2, 256


def _pool_copies(hlo: str, pool_shape) -> list:
    """Slices and dynamic-update-slices whose output is one layer's pool
    (``[N,bs,K,D]``, ``[1,N,bs,K,D]``) or the layer-stacked pool."""
    dims = ",".join(map(str, pool_shape))
    return re.findall(
        rf"= bf16\[(?:\d+,)?{dims}\]\{{[^}}]*\}} "
        r"(slice|slice-start|dynamic-slice|dynamic-update-slice)\(", hlo)


@pytest.mark.parametrize("name,d_model,d_ff,vocab,H,K,D,in_place", [
    ("mistral-7b-v0.3", 4096, 14336, 32768, 32, 8, 128, True),
    ("phi3-mini-4k", 3072, 8192, 32064, 32, 32, 96, False),
])
def test_paged_step_writes_lane_aligned_pool_in_place(
        topo, monkeypatch, name, d_model, d_ff, vocab, H, K, D, in_place):
    """With D=128 the step scatters into the stacked pool: no layer's pool
    is sliced out or written back. With D=96 the TPU keeps the block axis
    innermost, and the step keeps the per-layer slice path."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg = ModelConfig(
        name=name, num_layers=STEP_LAYERS, d_model=d_model, d_ff=d_ff,
        vocab_size=vocab, remat="none",
        attention=AttentionConfig(kind="gqa", num_heads=H, num_kv_heads=K,
                                  head_dim=D))
    run = RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                    sharding=ShardingConfig(fsdp_params=False, seq_axis=None))
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    with mesh:
        b = make_paged_serve_step(
            cfg, run, mesh, slots=SLOTS, chunk=DEFAULT_CHUNK,
            num_blocks=STEP_BLOCKS, block_size=BLOCK_SIZE,
            max_blocks_per_seq=MAX_BLOCKS_PER_SEQ, kernel="pallas")
        hlo = jax.jit(b.fn, in_shardings=b.in_shardings,
                      out_shardings=b.out_shardings, donate_argnums=(1,)
                      ).lower(*b.abstract_inputs).compile().as_text()
    assert hlo.count("tpu_custom_call") >= STEP_LAYERS
    assert b.meta["kv_pool_layers"] == STEP_LAYERS
    assert b.meta["kv_pool_in_place_layers"] == (STEP_LAYERS if in_place
                                                 else 0)
    copies = _pool_copies(hlo, (STEP_BLOCKS, BLOCK_SIZE, K, D))
    if in_place:
        assert copies == []
    else:
        assert "slice" in copies and "dynamic-update-slice" in copies


def test_mla_step_holds_only_the_latent_kernel_and_writes_in_place(
        topo, monkeypatch):
    """DeepSeek-V2-Lite's block at its published widths, two expert layers
    over a 640-lane latent pool: the compiled step's only Mosaic kernel is
    the latent kernel, one call a layer (the held experts are XLA
    matmuls), and no layer's pool is sliced out or written back."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "bench"))
    import spec
    from blocks import mla_moe

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    full = spec.read_json(os.path.join(os.path.dirname(__file__), "..",
                                       "bench", "configs",
                                       "deepseek-v2-lite-ep4.json"))
    g = mla_moe.geometry(dict(full, num_hidden_layers=STEP_LAYERS,
                              first_k_dense_replace=0), MAX_BLOCKS_PER_SEQ
                         * BLOCK_SIZE)
    cfg = mla_moe.model_config(g, "bfloat16")
    run = RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                    sharding=ShardingConfig(fsdp_params=False, seq_axis=None))
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    with mesh:
        b = make_paged_serve_step(
            cfg, run, mesh, slots=SLOTS, chunk=DEFAULT_CHUNK,
            num_blocks=STEP_BLOCKS, block_size=BLOCK_SIZE,
            max_blocks_per_seq=MAX_BLOCKS_PER_SEQ, kernel="pallas")
        hlo = jax.jit(b.fn, in_shardings=b.in_shardings,
                      out_shardings=b.out_shardings, donate_argnums=(1,)
                      ).lower(*b.abstract_inputs).compile().as_text()
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == STEP_LAYERS
    assert all("paged_latent_attention" in line for line in calls)
    assert "ragged" not in hlo
    assert b.meta["kv_pool_in_place_layers"] == STEP_LAYERS
    assert b.meta["moe_counters"]
    assert _pool_copies(hlo, (STEP_BLOCKS, BLOCK_SIZE, 640)) == []
