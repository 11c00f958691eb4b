"""Sharding-rule resolution properties (AbstractMesh — no devices needed)."""
import jax
import jax.numpy as jnp
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property tests need hypothesis (see requirements-dev.txt)")
from hypothesis import given, settings, strategies as st
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro.configs.base import ShardingConfig
from repro.runtime import mesh_util

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))

LOGICAL = st.sampled_from([None, "embed", "vocab", "ff", "moe_ff", "expert",
                           "heads", "kv_heads", "layer", "head_dim"])


def _rules(mesh, fsdp=True, dp=None):
    dp = dp or (("pod", "data") if "pod" in mesh.axis_names else ("data",))
    return mesh_util.make_rules(
        ShardingConfig(dp_axes=dp, tp_axis="model", fsdp_params=fsdp), mesh)


@settings(max_examples=60, deadline=None)
@given(st.lists(st.tuples(LOGICAL, st.sampled_from([1, 3, 16, 48, 256, 2560])),
                min_size=1, max_size=4),
       st.sampled_from([MESH, MESH3]))
def test_spec_always_valid(dims, mesh):
    """Every resolved spec divides its dims and uses each axis at most once."""
    rules = _rules(mesh)
    axes = tuple(d[0] for d in dims)
    shape = tuple(d[1] for d in dims)
    spec = mesh_util.spec_for(axes, shape, rules, mesh)
    sizes = dict(mesh.shape)
    used = []
    for entry, dim in zip(tuple(spec) + (None,) * (len(shape) - len(spec)),
                          shape):
        if entry is None:
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        prod = 1
        for a in names:
            prod *= sizes[a]
            used.append(a)
        assert dim % prod == 0, (axes, shape, spec)
    assert len(used) == len(set(used)), f"axis reused: {spec}"


def test_tp_preferred_fsdp_fallback():
    rules = _rules(MESH)
    # 2560 % 16 == 0 -> tp on the vocab dim
    assert mesh_util.spec_for(("vocab", "embed"), (2560, 2048), rules, MESH) \
        == P("model", ("data",))
    # heads=8 cannot split 16 ways -> replicated on that dim
    spec = mesh_util.spec_for(("embed", "heads", "head_dim"),
                              (2048, 8, 128), rules, MESH)
    assert spec == P(("data",), None, None)


def test_no_fsdp_means_replicated_embed():
    rules = _rules(MESH, fsdp=False)
    spec = mesh_util.spec_for(("embed", "ff"), (2048, 8192), rules, MESH)
    assert spec == P(None, "model")


def test_dp_extent_and_vocab_axis():
    rules = _rules(MESH3)
    assert mesh_util.dp_extent(rules, MESH3) == 32
    assert mesh_util.tp_vocab_axis(rules, MESH3, 128256) == "model"
    assert mesh_util.tp_vocab_axis(rules, MESH3, 504) is None     # 504 % 16


def test_batch_spec_dp_ok():
    rules = _rules(MESH)
    assert mesh_util.batch_spec(rules) == P("data", None)
    assert mesh_util.batch_spec(rules, dp_ok=False) == P(None, None)
    rules_sp = mesh_util.make_rules(
        ShardingConfig(dp_axes=("data",), seq_axis="model"), MESH)
    assert mesh_util.batch_spec(rules_sp, seq_sharded=True) \
        == P("data", "model")


def test_cache_spec_tree_shards_kv_heads():
    rules = _rules(MESH)
    cache = {"k": jax.ShapeDtypeStruct((32, 1024, 16, 128), jnp.bfloat16),
             "state": jax.ShapeDtypeStruct((32, 64, 16), jnp.float32),
             "scalar": jax.ShapeDtypeStruct((), jnp.int32)}
    specs = mesh_util.cache_spec_tree(cache, rules, MESH, batch=32)
    assert specs["k"] == P(("data",), None, "model", None)
    assert specs["scalar"] == P()
    seq = mesh_util.cache_spec_tree(cache, rules, MESH, batch=32,
                                    seq_sharded=True)
    # without a seq axis in rules nothing changes
    assert seq["k"] == P(("data",), None, "model", None)


def test_cache_spec_tree_layer_stacked_leaves():
    """Stacked (L, B, T, K, D) leaves: batch located structurally, the
    layer dim never sharded (the §Perf serving-sweep regression)."""
    rules = mesh_util.make_rules(
        ShardingConfig(dp_axes=("data",), fsdp_params=False,
                       seq_axis="model"), MESH)
    cache = {"k": jax.ShapeDtypeStruct((32, 128, 32768, 32, 80),
                                       jnp.bfloat16),
             "small_kv": jax.ShapeDtypeStruct((32, 128, 32768, 4, 80),
                                              jnp.bfloat16)}
    specs = mesh_util.cache_spec_tree(cache, rules, MESH, batch=128,
                                      seq_sharded=True)
    # kv-heads divisible (32 % 16): head-sharded, layer dim untouched
    assert specs["k"] == P(None, "data", None, "model", None)
    # kv=4 indivisible: falls back to seq sharding
    assert specs["small_kv"] == P(None, "data", "model", None, None)
