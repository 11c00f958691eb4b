"""DeepSeek-V2's latent attention through the paged pool.

The pool holds one latent row a token a layer (``[c_kv | k_rope | 0]``);
the latent kernel scores every head's absorbed query against it and
averages its compressed kv. These tests tie that path to attention as
published (not absorbed) and the whole paged Engine to the ``mla_moe``
block's float32 reference (``bench/blocks/mla_moe/reference.py``), whose
weights are rebuilt from the same seed.
"""
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SHAPES, RunConfig, ShardingConfig
from repro.engine import Engine, Request
from repro.engine.teacher_forcing import teacher_forced_logits
from repro.kernels.paged_attention import (latent_kernel_counts,
                                           paged_latent_attention,
                                           paged_latent_attention_ref)
from repro.launch.mesh import make_local_mesh
from repro.models import model as model_lib
from repro.models.kvcache import PagedLayout

BENCH = os.path.join(os.path.dirname(__file__), "..", "bench")
sys.path.insert(0, BENCH)
import spec  # noqa: E402
from blocks import mla_moe  # noqa: E402
from blocks.mla_moe import reference as ref  # noqa: E402
from blocks.mla_moe import weights as W  # noqa: E402

FULL = spec.read_json(os.path.join(BENCH, "configs",
                                   "deepseek-v2-lite-ep4.json"))
TINY = dict(FULL, name="tiny-mla", hidden_size=64, intermediate_size=96,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=16,
            v_head_dim=16, num_attention_heads=4, num_hidden_layers=3,
            moe_intermediate_size=32, n_routed_experts=4, vocab_size=256,
            num_experts_per_tok=3, published={"n_routed_experts": 8})


# Rows of the kernel tests, (start, n_valid) each, with the table entries
# past a row's resident blocks set to -1 where `unallocated` names the row.
# "m5": M = 5 below P, so P clamps to M and a row is one grid step. "m34":
# bs 16, so P = 16 over 34 entries, three grid steps a row, the last of two
# entries; its rows are a prefill chunk whose 32 blocks end on a step
# boundary, a decode row one block past it, a row with no live block, a
# short decode row whose table is -1 past its 3 blocks, a first chunk, and
# a chunk that fills all 34 entries. "m20": two grid steps a row, the last
# of four entries, between idle rows: the call's first live step is not
# its first grid step, and no live step follows its last row with tokens.
LATENT_CASES = {
    "m5": dict(bs=4, M=5, starts=[0, 9, 6], n_valid=[4, 3, 1],
               unallocated=[2]),
    "m34": dict(bs=16, M=34, starts=[500, 516, 0, 40, 0, 540],
                n_valid=[4, 1, 0, 1, 4, 4], unallocated=[1, 3]),
    "m20": dict(bs=16, M=20, starts=[0, 0, 0, 300, 0],
                n_valid=[0, 2, 0, 4, 0], unallocated=[1]),
}
# (grid steps, live grid steps, pool rows DMA'd) of one kernel call, by hand
LATENT_COUNTS = {"m5": (3, 3, 24), "m34": (18, 10, 1648),
                 "m20": (10, 3, 320)}


def _latent_case(rng, dtype, case="m5", *, C=4, H=4, r=32, dr=16):
    """Random absorbed queries, a pool holding random latent rows at the
    rows' block-table positions (drawn out of order from the pool), and
    the rows' (start, n)."""
    spec = LATENT_CASES[case]
    bs, M = spec["bs"], spec["M"]
    B, lanes = len(spec["starts"]), 128
    N = B * M + 2
    pool = np.zeros((N, bs, lanes), np.float32)
    pool[:, :, :r + dr] = rng.normal(size=(N, bs, r + dr))
    tables = rng.permutation(N)[:B * M].reshape(B, M).astype(np.int32)
    starts = np.array(spec["starts"], np.int32)
    n_valid = np.array(spec["n_valid"], np.int32)
    for b in spec["unallocated"]:
        tables[b, -(-(starts[b] + n_valid[b]) // bs):] = -1
    q = np.zeros((B, C, H, lanes), np.float32)
    q[..., :r + dr] = rng.normal(size=(B, C, H, r + dr))
    cast = lambda a: jnp.asarray(a, dtype)
    return (cast(q), cast(pool), jnp.asarray(tables), jnp.asarray(starts),
            jnp.asarray(n_valid)), dict(block_size=bs, v_lanes=r)


def _unabsorbed(q, pool, tables, starts, n_valid, *, block_size, v_lanes,
                w_uk, w_uv, q_nope, scale):
    """Published MLA of each valid column: per-head keys [c W_UK | k_rope]
    and values c W_UV over the row's causal context, in float64."""
    r, dr = v_lanes, 16
    out = {}
    for b in range(q.shape[0]):
        rows = np.concatenate([np.asarray(pool[t]) for t in tables[b]
                               if t >= 0])
        for c in range(int(n_valid[b])):
            pos = int(starts[b]) + c
            ctx = rows[:pos + 1].astype(np.float64)
            ck, kr = ctx[:, :r], ctx[:, r:r + dr]
            k_nope = np.einsum("tr,rhn->thn", ck, w_uk)
            v = np.einsum("tr,rhv->thv", ck, w_uv)
            s = (np.einsum("hn,thn->ht", q_nope[b, c], k_nope)
                 + np.einsum("hd,td->ht", np.asarray(q[b, c, :, r:r + dr],
                                                     np.float64), kr))
            p = np.exp(scale * s - (scale * s).max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            out[b, c] = np.einsum("ht,thv->hv", p, v)
    return out


@pytest.mark.parametrize("kernel,case", [("ref", "m5"), ("pallas", "m5"),
                                         ("pallas", "m34"), ("pallas", "m20")],
                         ids=["ref", "pallas", "pallas-m34", "pallas-m20"])
def test_latent_kernel_matches_unabsorbed_attention(kernel, case):
    """Absorbed through the latent pool equals attention as published, in
    float32 to 2e-5 (float32 rounding of sums over 48 lanes and up to 544
    keys reads ~1e-6); the same inputs rounded to bf16 read ~9e-3, past
    2e-3."""
    rng = np.random.default_rng(0)
    H, r, dn = 4, 32, 8
    B = len(LATENT_CASES[case]["starts"])
    w_uk = rng.normal(size=(r, H, dn)) / math.sqrt(r)
    w_uv = rng.normal(size=(r, H, 8)) / math.sqrt(r)
    q_nope = rng.normal(size=(B, 4, H, dn))
    args, kw = _latent_case(rng, jnp.float32, case, H=H, r=r)
    q = np.asarray(args[0]).copy()
    q[..., :r] = np.einsum("bchn,rhn->bchr", q_nope, w_uk)
    scale = 0.3
    fn = paged_latent_attention if kernel == "pallas" else \
        paged_latent_attention_ref
    want = _unabsorbed(jnp.asarray(q), *args[1:], **kw, w_uk=w_uk, w_uv=w_uv,
                       q_nope=q_nope, scale=scale)

    def err(q_in, pool):
        got = np.asarray(fn(q_in, pool, *args[2:], scale=scale, **kw),
                         np.float64)
        return max(np.abs(np.einsum("hr,rhv->hv", got[b, c], w_uv)
                          - w).max() for (b, c), w in want.items())

    e32 = err(jnp.asarray(q), args[1])
    bf = lambda a: jnp.asarray(a, jnp.bfloat16).astype(jnp.float32)
    e16 = err(bf(q), bf(args[1]))
    assert e32 < 2e-5
    assert e16 > 2e-3


def test_latent_pallas_kernel_matches_ref_in_bf16():
    args, kw = _latent_case(np.random.default_rng(1), jnp.bfloat16)
    got = paged_latent_attention(*args, scale=0.2, **kw)
    want = paged_latent_attention_ref(*args, scale=0.2, **kw)
    valid = np.arange(4)[None, :] < np.asarray(args[4])[:, None]
    np.testing.assert_allclose(np.asarray(got, np.float32)[valid],
                               np.asarray(want, np.float32)[valid],
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(LATENT_CASES))
def test_latent_pallas_kernel_matches_ref(case, dtype, tol):
    """The kernel against ref.py on every case's valid columns; a row with
    no live block comes back as zeros."""
    args, kw = _latent_case(np.random.default_rng(2), dtype, case)
    got = np.asarray(paged_latent_attention(*args, scale=0.2, **kw),
                     np.float32)
    want = np.asarray(paged_latent_attention_ref(*args, scale=0.2, **kw),
                      np.float32)
    n_valid = np.asarray(args[4])
    valid = np.arange(4)[None, :] < n_valid[:, None]
    np.testing.assert_allclose(got[valid], want[valid], atol=tol, rtol=tol)
    assert not got[n_valid == 0].any()


@pytest.mark.parametrize("case", sorted(LATENT_CASES))
def test_latent_kernel_counts_match_hand_counts(case):
    spec = LATENT_CASES[case]
    assert latent_kernel_counts(
        spec["starts"], spec["n_valid"], block_size=spec["bs"],
        max_blocks=spec["M"]) == LATENT_COUNTS[case]


def test_latent_pool_write_drops_invalid_columns():
    layout = PagedLayout(jnp.asarray([[2, 0], [1, -1]], jnp.int32),
                         jnp.asarray([3, 0], jnp.int32),
                         jnp.asarray([2, 1], jnp.int32), 4)
    pool = jnp.zeros((3, 4, 128), jnp.float32)
    new = jnp.arange(2 * 3 * 128, dtype=jnp.float32).reshape(2, 3, 128) + 1
    out = np.asarray(layout.write(pool, new))
    np.testing.assert_array_equal(out[2, 3], new[0, 0])   # position 3
    np.testing.assert_array_equal(out[0, 0], new[0, 1])   # position 4
    np.testing.assert_array_equal(out[1, 0], new[1, 0])
    assert np.count_nonzero(out.any(-1)) == 3


def test_full_width_pool_is_written_in_place_in_every_layer():
    g = mla_moe.geometry(FULL, 4607)
    cfg = mla_moe.model_config(g, "bfloat16")
    cache = jax.eval_shape(lambda: model_lib.init_paged_cache(cfg, 6912, 16))
    leaves = jax.tree.leaves(cache)
    assert [t.shape for t in leaves] == [(6912, 16, 640), (26, 6912, 16, 640)]
    assert model_lib.paged_in_place_layers(cfg, cache) == (27, 27)
    assert mla_moe.kv_bytes_per_token(g) == 34_560


# ---------------------------------------------------------------------------
# the paged Engine against the block's float32 reference
# ---------------------------------------------------------------------------

SEED = 2 ** 33 + 17
# A served logit's distance from the float32 reference, in standard
# deviations of the reference's logits at that position. The bf16 Engine
# reads 0.091 at most here; the reference with every matmul operand rounded
# through float8 (the next precision down) reads 0.52 on the same tokens.
# The limit lies between, about twice from each.
LOGIT_TOL = 0.2


def _reference_logits(g, tokens, quant=None):
    lo, hi = W.seed_words(SEED)
    x = ref.embed(g, lo, hi, jnp.asarray(tokens))
    for i in range(g.layers):
        apply = ref.dense_layer if i < g.dense_layers else ref.layer
        x = apply(g, x, lo, hi, jnp.uint32(i), quant)
    with jax.default_matmul_precision("highest"):
        norm = W.top_leaf(g, lo, hi, "final_norm").astype(jnp.float32)
        head = ref._w(W.top_leaf(g, lo, hi, "head"), quant)
        h = ref._rms_norm(x, norm, g.norm_eps)
        return np.asarray(ref._a(h, quant) @ head)


def test_paged_engine_matches_float32_reference():
    """Four requests on three slots and a pool too small for all of them:
    prompts prefill in 4-token chunks beside decode rows, one request is
    preempted and resumed. Every served position's logits, fed as the
    Engine feeds them, lie within LOGIT_TOL of the reference's; every
    served token is the reference's best or within LOGIT_TOL of it; the
    fp8 control lies beyond LOGIT_TOL."""
    g = mla_moe.geometry(TINY, 64)
    cfg = mla_moe.model_config(g, "bfloat16")
    run = RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                    sharding=ShardingConfig(fsdp_params=False, seq_axis=None))
    mesh = make_local_mesh((1, 1))
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, g.vocab, n).astype(np.int32)
               for n in (13, 22, 9, 17)]
    with mesh:
        eng = Engine(cfg, run, mesh, cache="paged", kernel="pallas",
                     slots=3, max_len=64, num_blocks=16, block_size=4,
                     chunk=4)
        params = jax.jit(lambda lo, hi: W.make_params(g, lo, hi),
                         out_shardings=eng.pshard)(*W.seed_words(SEED))
        eng.load_params(params)
        for rid, p in enumerate(prompts):
            eng.submit(Request(rid, p, max_new_tokens=10))
        done = eng.run_until_drained()
        assert eng.preempt_count > 0
        m = eng.metrics()
        assert m["kv_pool_layers"] == 3
        # 3 latent layers x 3 rows x one grid step (P clamps to M = 16)
        # for each step of 3 x 4 token slots; a live step fetches a block
        # of 4 rows at least
        assert m["latent_kv_steps"] == 9 * m["step_token_slots"] // 12
        assert 0 < m["latent_kv_steps_live"] <= m["latent_kv_steps"]
        assert m["latent_rows_fetched"] % 4 == 0
        assert m["latent_rows_fetched"] >= 4 * m["latent_kv_steps_live"]
        seqs = [list(p) + list(r.out_tokens[:-1])
                for p, r in zip(prompts, sorted(done, key=lambda r: r.rid))]
        got = teacher_forced_logits(eng, seqs, [len(p) for p in prompts])
    P = max(len(s) for s in seqs)
    tokens = np.zeros((len(seqs), P), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, :len(s)] = s
    want = _reference_logits(g, tokens)
    fp8 = _reference_logits(g, tokens, "fp8")
    served = {r.rid: r.out_tokens for r in done}
    worst = worst8 = 0.0
    for i, s in enumerate(seqs):
        L = len(prompts[i])
        w, f, gt = want[i, :len(s)], fp8[i, :len(s)], got[i]
        std = w.std(-1, keepdims=True)
        worst = max(worst, float((np.abs(gt - w) / std)[L - 1:].max()))
        worst8 = max(worst8, float((np.abs(f - w) / std)[L - 1:].max()))
        rows = w[L - 1:L - 1 + len(served[i])]
        picked = rows[np.arange(len(rows)), served[i]]
        gap = (rows.max(-1) - picked) / rows.std(-1)
        assert gap.max() <= LOGIT_TOL, (i, gap)
    assert worst <= LOGIT_TOL, worst
    assert worst8 > LOGIT_TOL, worst8
