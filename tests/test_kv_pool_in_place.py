"""The paged step writes a lane-aligned KV pool in place.

Where a pool leaf's (K, D) fill whole (8, 128) tiles
(``model.pool_writes_in_place``), the unrolled paged layer loop hands each
layer a flat ``(L*N, bs, K, D)`` view of the stacked pool and block tables
offset by ``r*N``, instead of slicing the layer's pool out and writing it
back. Both paths must serve the same tokens and leave the same pool, bit for
bit: the slice path is reached here by patching the rule to refuse.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.configs.base import (SHAPES, AttentionConfig, ModelConfig,
                                RunConfig, ShardingConfig)
from repro.engine import Engine, Request
from repro.models import model as model_lib
from repro.runtime.steps import make_paged_serve_step

SLOTS, CHUNK, BLOCK, NUM_BLOCKS, MAX_BLOCKS = 3, 4, 4, 12, 4


def _cfg(pattern: str = "dense", head_dim: int = 128) -> ModelConfig:
    """Toy decoder at a lane-aligned head width: 8 heads over 8 kv heads.
    ``local_global`` repeats (local, local, full) twice: two block types,
    each with its own stacked pool, in one repeated pattern."""
    local = pattern == "local_global"
    return ModelConfig(
        name=f"toy-{pattern}-d{head_dim}", num_layers=6 if local else 3,
        d_model=64, d_ff=128, vocab_size=256,
        attention=AttentionConfig(
            kind="gqa", num_heads=8, num_kv_heads=8, head_dim=head_dim,
            sliding_window=6 if local else None,
            local_global_ratio=2 if local else 0),
        tie_embeddings=True, remat="none")


def _run(cfg):
    return RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                     sharding=ShardingConfig(fsdp_params=False,
                                             seq_axis=None))


def _mesh(dp: int, tp: int) -> Mesh:
    devs = jax.devices()
    if len(devs) < dp * tp:
        pytest.skip(f"needs {dp * tp} devices, have {len(devs)}")
    return Mesh(np.array(devs[:dp * tp]).reshape(dp, tp), ("data", "model"))


def _slice_path(monkeypatch):
    monkeypatch.setattr(model_lib, "pool_writes_in_place", lambda pool: False)


def _ticks():
    """(tokens, tables, starts, n_valid) per tick. Row 0 prefills a 6-token
    prompt in two chunks, then decodes; row 1 decodes from 9 resident tokens
    beside it; row 2 is idle, then starts a prefill. Unallocated table slots
    are -1."""
    rng = np.random.default_rng(0)
    tables = np.full((SLOTS, MAX_BLOCKS), -1, np.int32)
    tables[0, :3] = [5, 2, 9]
    tables[1, :4] = [0, 7, 3, 11]
    tables[2, :2] = [4, 10]
    rows = [((0, 4), (9, 1), (0, 0)),
            ((4, 2), (10, 1), (0, 4)),
            ((6, 1), (11, 1), (4, 3)),
            ((7, 1), (12, 1), (7, 1))]
    for row in rows:
        starts = np.array([s for s, _ in row], np.int32)
        n_valid = np.array([n for _, n in row], np.int32)
        tokens = rng.integers(0, 256, (SLOTS, CHUNK)).astype(np.int32)
        yield tokens, tables, starts, n_valid


def _step_run(cfg, kernel):
    """Tokens (real rows only) and pool after each of the ticks."""
    mesh = _mesh(1, 1)
    with mesh:
        b = make_paged_serve_step(
            cfg, _run(cfg), mesh, slots=SLOTS, chunk=CHUNK,
            num_blocks=NUM_BLOCKS, block_size=BLOCK,
            max_blocks_per_seq=MAX_BLOCKS, kernel=kernel)
        params = model_lib.init_params(cfg, jax.random.PRNGKey(1))[0]
        # stale pool content, as reused blocks hold
        leaves, tree = jax.tree.flatten(model_lib.init_paged_cache(
            cfg, NUM_BLOCKS, BLOCK))
        keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
        cache = jax.tree.unflatten(tree, [
            jax.random.normal(k, t.shape, t.dtype)
            for k, t in zip(keys, leaves)])
        step = jax.jit(b.fn)
        out = []
        for tokens, tables, starts, n_valid in _ticks():
            tok, cache = step(params, cache, tokens, tables, starts, n_valid)
            out.append((np.asarray(tok)[n_valid > 0],
                        jax.tree.map(np.asarray, cache)))
    return out, b.meta


@pytest.mark.parametrize("kernel", ["ref", "pallas"])
@pytest.mark.parametrize("pattern", ["dense", "local_global"])
def test_in_place_step_matches_slice_path(monkeypatch, pattern, kernel):
    cfg = _cfg(pattern)
    got, meta = _step_run(cfg, kernel)
    assert meta["kv_pool_in_place_layers"] == cfg.num_layers
    with monkeypatch.context() as m:
        _slice_path(m)
        want, meta = _step_run(cfg, kernel)
    assert meta["kv_pool_in_place_layers"] == 0
    for t, ((tok, pool), (tok_w, pool_w)) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(tok, tok_w, err_msg=f"tick {t}")
        for a, w in zip(jax.tree.leaves(pool), jax.tree.leaves(pool_w)):
            np.testing.assert_array_equal(a, w, err_msg=f"tick {t}")


def _greedy_reference(cfg, params, prompt, n):
    params = jax.device_get(params)
    toks = [int(t) for t in prompt]
    out = []
    for _ in range(n):
        logits, _, _ = model_lib.forward(cfg, params,
                                         jnp.asarray([toks], jnp.int32))
        out.append(int(jnp.argmax(logits[0, -1])))
        toks.append(out[-1])
    return out


def _serve(cfg, mesh, prompts, new_tokens):
    with mesh:
        eng = Engine(cfg, _run(cfg), mesh, cache="paged", kernel="pallas",
                     slots=SLOTS, max_len=16, num_blocks=16, block_size=BLOCK,
                     chunk=CHUNK)
        eng.load_params()
        for rid, p in enumerate(prompts):
            eng.submit(Request(rid, p, max_new_tokens=new_tokens))
        done = eng.run_until_drained()
    return ({r.rid: list(r.out_tokens) for r in done},
            jax.tree.map(np.asarray, eng.cache), eng)


@pytest.mark.parametrize("dp,tp", [(1, 1), (1, 2)])
def test_engine_in_place_serves_like_slice_path(monkeypatch, dp, tp):
    """Four requests on three slots: prompts longer than the chunk prefill
    beside decode rows, slots fall idle and are reused. On (1, 2) the pool
    and the kernel shard their kv heads over tp."""
    cfg = _cfg()
    mesh = _mesh(dp, tp)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32)
               for n in rng.integers(3, 11, 4)]
    got, pool, eng = _serve(cfg, mesh, prompts, 4)
    assert eng.metrics()["kv_pool_in_place_layers"] == cfg.num_layers
    with monkeypatch.context() as m:
        _slice_path(m)
        want, pool_w, _ = _serve(cfg, mesh, prompts, 4)
    assert got == want
    for a, w in zip(jax.tree.leaves(pool), jax.tree.leaves(pool_w)):
        np.testing.assert_array_equal(a, w)
    for rid, p in enumerate(prompts):
        assert got[rid] == _greedy_reference(cfg, eng.params, p, 4), rid


@pytest.mark.parametrize("head_dim,in_place", [(128, 3), (64, 0)])
def test_engine_counts_in_place_layers(head_dim, in_place):
    cfg = _cfg(head_dim=head_dim)
    mesh = _mesh(1, 1)
    with mesh:
        eng = Engine(cfg, _run(cfg), mesh, cache="paged", slots=SLOTS,
                     max_len=32, num_blocks=16, block_size=BLOCK,
                     chunk=CHUNK)
    m = eng.metrics()
    assert (m["kv_pool_in_place_layers"], m["kv_pool_layers"]) == (
        in_place, cfg.num_layers)


@pytest.mark.parametrize("shape,in_place", [
    ((2, 16, 16, 8, 128), True),       # Mistral-7B: K=8, D=128
    ((2, 16, 16, 16, 256), True),
    ((2, 16, 16, 32, 96), False),      # Phi-3-mini: D=96
    ((2, 16, 16, 8, 64), False),       # llama3.2-1b: D=64
    ((2, 16, 16, 2, 128), False),      # K short of the 8-row tile
])
def test_pool_writes_in_place_rule(shape, in_place):
    leaf = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    assert model_lib.pool_writes_in_place([{"k": leaf, "v": leaf}]) is in_place


def test_counter_covers_every_group():
    cfg = dataclasses.replace(_cfg("local_global"), num_layers=7)
    cache = jax.eval_shape(lambda: model_lib.init_paged_cache(cfg, 8, 4))
    # (local, local, full) x 2, then one unstacked local layer
    assert [len(p) * r for p, r in model_lib.layer_plan(cfg)] == [6, 1]
    assert model_lib.paged_in_place_layers(cfg, cache) == (7, 7)
