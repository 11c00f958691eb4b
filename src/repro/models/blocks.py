"""Decoder/encoder block assembly per block type.

A *block type* is a string key ("attn_full", "attn_local", "attn_moe",
"mla_dense", "mla_moe", "hybrid_local", "hybrid_full", "mlstm", "slstm",
"enc") — ``repro.models.model.layer_plan`` arranges them into repeated-pattern
groups that are executed under ``lax.scan`` with stacked parameters.

Every block has the same signature so the scan body can be uniform:
    apply(bt, params, x, cfg, cache, length, positions, mrope, transport)
      -> (x_out, new_cache_dict, aux_loss)
cache dicts hold raw arrays (no dataclass) so they stack/slice trivially.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.common import ParamBuilder, rms_norm
from repro.models.kvcache import (KVCache, MLACache, PagedKVCache,
                                  PagedLayout, RecurrentLayout, SSMCache)

Cache = Optional[Dict[str, Any]]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_block(b: ParamBuilder, bt: str, cfg: ModelConfig) -> None:
    d = cfg.d_model
    b.param("ln1", (d,), ("embed",), init="zeros")
    if bt in ("mlstm",):
        xlstm_mod.init_mlstm(b.scope("mlstm"), d, cfg.xlstm)
        return
    if bt in ("slstm",):
        xlstm_mod.init_slstm(b.scope("slstm"), d, cfg.xlstm)
        return
    if bt == "ssm":
        # pure selective-SSM block (mamba): norm -> SSM residual, plus an
        # optional MLP residual when the arch carries one (d_ff > 0)
        ssm_mod.init_ssm(b.scope("ssm"), d, cfg.ssm)
        if cfg.d_ff:
            b.param("ln2", (d,), ("embed",), init="zeros")
            mlp_mod.init_mlp(b.scope("mlp"), d, cfg.d_ff, cfg.mlp_gated)
        return
    b.param("ln2", (d,), ("embed",), init="zeros")
    a = cfg.attention
    if bt.startswith("mla"):
        attn.init_mla(b.scope("attn"), d, a)
    else:
        attn.init_gqa(b.scope("attn"), d, a)
    if bt.startswith("hybrid"):
        ssm_mod.init_ssm(b.scope("ssm"), d, cfg.ssm)
    if bt.endswith("_moe"):
        moe_mod.init_moe(b.scope("moe"), d, cfg.moe)
    else:
        mlp_mod.init_mlp(b.scope("mlp"), d, cfg.d_ff, cfg.mlp_gated)


# ---------------------------------------------------------------------------
# cache init (raw-array dicts; length lives at model level)
# ---------------------------------------------------------------------------

def init_block_cache(bt: str, cfg: ModelConfig, batch: int, max_len: int,
                     dtype=jnp.bfloat16) -> Dict[str, Any]:
    a = cfg.attention
    c: Dict[str, Any] = {}
    if bt in ("mlstm",):
        kc = xlstm_mod.mlstm_init_cache(cfg.d_model, cfg.xlstm, batch, dtype)
        return {"conv": kc.conv, "state": kc.state, "n": kc.extra[0], "m": kc.extra[1]}
    if bt in ("slstm",):
        kc = xlstm_mod.slstm_init_cache(cfg.d_model, cfg.xlstm, batch, dtype)
        return {"state": kc.state, "c": kc.extra[0], "n": kc.extra[1], "m": kc.extra[2]}
    if bt == "ssm":
        sc = ssm_mod.ssm_init_cache(cfg.d_model, cfg.ssm, batch, dtype)
        return {"conv": sc.conv, "state": sc.state}
    if bt.startswith("mla"):
        c["c_kv"] = jnp.zeros((batch, max_len, a.kv_lora_rank), dtype)
        c["k_rope"] = jnp.zeros((batch, max_len, a.qk_rope_head_dim), dtype)
    else:
        kv_shape = (batch, max_len, a.num_kv_heads, a.head_dim)
        c["k"] = jnp.zeros(kv_shape, dtype)
        c["v"] = jnp.zeros(kv_shape, dtype)
    if bt.startswith("hybrid"):
        sc = ssm_mod.ssm_init_cache(cfg.d_model, cfg.ssm, batch, dtype)
        c["conv"] = sc.conv
        c["state"] = sc.state
    return c


# ---------------------------------------------------------------------------
# paged cache init (serving pool; GQA block types only)
# ---------------------------------------------------------------------------

# Block types the paged serving pool holds: GQA k/v leaves, or MLA's one
# latent leaf (compressed kv and rotary key, ``attn.mla_latent_lanes``).
PAGED_BLOCK_TYPES = ("attn_full", "attn_local", "attn_moe", "mla_dense",
                     "mla_moe")

# Block types whose per-request state is constant-size (conv history +
# recurrent state, no seq-length axis) — the ones the recurrent serving
# backend supports. Hybrid blocks carry seq-sized KV leaves alongside the
# SSM state, so they are excluded (use cache='slots' for those archs).
RECURRENT_BLOCK_TYPES = ("mlstm", "slstm", "ssm")


def init_paged_block_cache(bt: str, cfg: ModelConfig, num_blocks: int,
                           block_size: int, dtype=jnp.bfloat16) -> Dict[str, Any]:
    if bt not in PAGED_BLOCK_TYPES:
        raise ValueError(
            f"paged serving supports block types {PAGED_BLOCK_TYPES}, "
            f"got {bt!r} — use cache='slots' for this arch")
    a = cfg.attention
    if bt.startswith("mla"):
        return {"latent": jnp.zeros(
            (num_blocks, block_size, attn.mla_latent_lanes(a)), dtype)}
    shape = (num_blocks, block_size, a.num_kv_heads, a.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


# ---------------------------------------------------------------------------
# apply
# ---------------------------------------------------------------------------

def apply_block(
    bt: str,
    params,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    cache: Cache = None,
    length: Optional[jax.Array] = None,
    positions: Optional[jax.Array] = None,
    mrope_positions: Optional[jax.Array] = None,
    moe_transport=None,
    paged: Optional[PagedLayout] = None,
    paged_kernel="auto",         # str kind or a sharded-kernel callable
    recurrent: Optional[RecurrentLayout] = None,
) -> Tuple[jax.Array, Cache, jax.Array]:
    a = cfg.attention
    zero = jnp.zeros((), jnp.float32)

    if paged is not None:
        return _apply_block_paged(bt, params, x, cfg, cache, paged,
                                  moe_transport, paged_kernel)

    if recurrent is not None and bt not in RECURRENT_BLOCK_TYPES:
        raise ValueError(
            f"block type {bt!r} has no recurrent serving path — only "
            f"{RECURRENT_BLOCK_TYPES} carry constant-size state; use "
            "cache='paged' or 'slots' for this arch")
    valid = recurrent.token_valid(x.shape[1]) if recurrent is not None else None

    if bt == "mlstm":
        h = rms_norm(x, params["ln1"], cfg.norm_eps)
        kc = None
        if cache is not None:
            kc = SSMCache(cache["conv"], cache["state"],
                          (cache["n"], cache["m"]), length)
        y, nkc = xlstm_mod.mlstm_forward(params["mlstm"], h, cfg.xlstm,
                                         cache=kc, valid=valid)
        new_cache = None
        if nkc is not None:
            new_cache = {"conv": nkc.conv, "state": nkc.state,
                         "n": nkc.extra[0], "m": nkc.extra[1]}
        return x + y, new_cache, zero

    if bt == "slstm":
        h = rms_norm(x, params["ln1"], cfg.norm_eps)
        kc = None
        if cache is not None:
            kc = SSMCache(cache.get("conv", jnp.zeros((x.shape[0], 0, 0), x.dtype)),
                          cache["state"], (cache["c"], cache["n"], cache["m"]),
                          length)
        y, nkc = xlstm_mod.slstm_forward(params["slstm"], h, cfg.xlstm,
                                         cache=kc, valid=valid)
        new_cache = None
        if nkc is not None:
            new_cache = {"state": nkc.state, "c": nkc.extra[0],
                         "n": nkc.extra[1], "m": nkc.extra[2]}
        return x + y, new_cache, zero

    if bt == "ssm":
        h = rms_norm(x, params["ln1"], cfg.norm_eps)
        sc = None
        if cache is not None:
            sc = SSMCache(cache["conv"], cache["state"], None, length)
        y, nsc = ssm_mod.ssm_forward(params["ssm"], h, cfg.ssm,
                                     cache=sc, valid=valid)
        new_cache = None
        if nsc is not None:
            new_cache = {"conv": nsc.conv, "state": nsc.state}
        x = x + y
        if cfg.d_ff:
            h2 = rms_norm(x, params["ln2"], cfg.norm_eps)
            x = x + mlp_mod.mlp(params["mlp"], h2, cfg.act, cfg.mlp_gated)
        return x, new_cache, zero

    # ---- attention (+ optional parallel SSM) sub-layer ----
    h = rms_norm(x, params["ln1"], cfg.norm_eps)
    causal = not cfg.is_encoder
    window = None
    if bt.endswith("_local") or (bt.startswith("attn_local")) or bt == "hybrid_local":
        window = a.sliding_window
    new_cache: Dict[str, Any] = {} if cache is not None else None

    if bt.startswith("mla"):
        mc = None
        if cache is not None:
            mc = MLACache(cache["c_kv"], cache["k_rope"], length)
        y_attn, nmc = attn.mla_attention(params["attn"], h, a, causal=causal,
                                         cache=mc, positions=positions,
                                         norm_eps=cfg.norm_eps)
        if nmc is not None:
            new_cache.update(c_kv=nmc.c_kv, k_rope=nmc.k_rope)
    else:
        kv = None
        if cache is not None:
            kv = KVCache(cache["k"], cache["v"], length)
        y_attn, nkv = attn.gqa_attention(params["attn"], h, a, causal=causal,
                                         window=window, cache=kv,
                                         positions=positions,
                                         mrope_positions=mrope_positions)
        if nkv is not None:
            new_cache.update(k=nkv.k, v=nkv.v)

    if bt.startswith("hybrid"):
        sc = None
        if cache is not None:
            sc = SSMCache(cache["conv"], cache["state"], None, length)
        y_ssm, nsc = ssm_mod.ssm_forward(params["ssm"], h, cfg.ssm, cache=sc)
        # hymba: mean-fuse the parallel attention and mamba head outputs
        y_attn = 0.5 * (y_attn + y_ssm)
        if nsc is not None:
            new_cache.update(conv=nsc.conv, state=nsc.state)

    x = x + y_attn

    # ---- FFN sub-layer ----
    h2 = rms_norm(x, params["ln2"], cfg.norm_eps)
    aux = zero
    if bt.endswith("_moe") and cfg.moe.experts_held is not None:
        y_ffn, _ = moe_mod.moe_ffn_held(params["moe"], h2, cfg.moe, cfg.act)
    elif bt.endswith("_moe"):
        y_ffn, aux = moe_mod.moe_ffn(params["moe"], h2, cfg.moe, cfg.act,
                                     transport=moe_transport)
    else:
        y_ffn = mlp_mod.mlp(params["mlp"], h2, cfg.act, cfg.mlp_gated)
    return x + y_ffn, new_cache, aux


def _apply_block_paged(bt: str, params, x: jax.Array, cfg: ModelConfig,
                       cache: Cache, paged: PagedLayout,
                       moe_transport, paged_kernel="auto"
                       ) -> Tuple[jax.Array, Cache, jax.Array]:
    """Paged-serving variant: attention through the block pool.

    Same residual structure as the contiguous path; only the attention
    sub-layer differs (pool scatter + the stash-resident kernel or its
    gather-then-dense oracle instead of contiguous append). MLA blocks
    write and read one latent leaf through the latent kernel; on a
    multi-device mesh (``paged_kernel`` a sharded GQA callable) it runs
    unsharded, as the latent has no head axis to split.

    An expert layer that holds a share of the experts
    (``MoEConfig.experts_held``) returns its counters (``moe.moe_ffn_held``)
    in the aux slot; the step reports them (``runtime.steps``).
    """
    if bt not in PAGED_BLOCK_TYPES:
        raise ValueError(f"block type {bt!r} has no paged path")
    a = cfg.attention
    with jax.named_scope("attention"):
        h = rms_norm(x, params["ln1"], cfg.norm_eps)
        if bt.startswith("mla"):
            kind = "pallas" if callable(paged_kernel) else paged_kernel
            y_attn, pool = attn.mla_paged_attention(
                params["attn"], h, a, pool=cache["latent"], layout=paged,
                kernel=kind, norm_eps=cfg.norm_eps)
            new_cache = {"latent": pool}
        else:
            window = a.sliding_window if bt.endswith("_local") else None
            pkv = PagedKVCache(cache["k"], cache["v"], paged.block_size)
            y_attn, npkv = attn.gqa_paged_attention(params["attn"], h, a,
                                                    cache=pkv, layout=paged,
                                                    window=window,
                                                    kernel=paged_kernel)
            new_cache = {"k": npkv.k_pool, "v": npkv.v_pool}
        x = x + y_attn
    if bt.endswith("_moe") and cfg.moe.experts_held is not None:
        with jax.named_scope("moe"):
            h2 = rms_norm(x, params["ln2"], cfg.norm_eps)
            y_ffn, aux = moe_mod.moe_ffn_held(
                params["moe"], h2, cfg.moe, cfg.act,
                token_mask=paged.token_valid(x.shape[1]))
        return x + y_ffn, new_cache, aux
    with jax.named_scope("mlp"):
        h2 = rms_norm(x, params["ln2"], cfg.norm_eps)
        if bt.endswith("_moe"):
            # mask the padding columns out of routing so they cannot steal
            # expert capacity from real tokens (same drop-slot rule on the
            # oracle and every jam transport — docs/fabric.md)
            y_ffn, aux = moe_mod.moe_ffn(
                params["moe"], h2, cfg.moe, cfg.act, transport=moe_transport,
                token_mask=paged.token_valid(x.shape[1]))
        else:
            y_ffn = mlp_mod.mlp(params["mlp"], h2, cfg.act, cfg.mlp_gated)
            aux = jnp.zeros((), jnp.float32)
        x = x + y_ffn
    return x, new_cache, aux
