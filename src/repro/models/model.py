"""The LM model: layer plan -> scanned repeated-pattern groups -> logits.

One model definition covers all 10 assigned architectures. Layers are grouped
into (pattern, repeats) *super-blocks*; parameters for each pattern position
are stacked over repeats and executed under ``lax.scan`` — this keeps the HLO
size O(#distinct block types) instead of O(#layers), which is what makes the
72B/80L dry-run compile quickly, and naturally expresses mixed stacks
(gemma3's 5:1 local:global, xLSTM's 7:1 mLSTM:sLSTM) with zero parameter
waste.

Entry points:
  init_params(cfg, key)               -> (params, logical_axes)
  forward(cfg, params, tokens, ...)   -> (logits, new_cache, aux)
  init_cache(cfg, batch, max_len)     -> cache pytree
  loss_fn(cfg, params, batch)         -> (loss, metrics)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import blocks as blocks_mod
from repro.models.common import ParamBuilder, rms_norm, softcap, stack_axes
from repro.models.kvcache import PagedLayout, RecurrentLayout

PyTree = Any


# ---------------------------------------------------------------------------
# Layer plan
# ---------------------------------------------------------------------------

def layer_plan(cfg: ModelConfig) -> List[Tuple[Tuple[str, ...], int]]:
    """[(pattern, repeats), ...] covering cfg.num_layers in order."""
    L = cfg.num_layers
    if cfg.xlstm is not None:
        k = cfg.xlstm.slstm_every
        if k and L >= k:
            pattern = ("mlstm",) * (k - 1) + ("slstm",)
            groups = [(pattern, L // k)]
            if L % k:
                groups.append((("mlstm",) * (L % k), 1))
            return groups
        return [(("mlstm",), L)]

    if cfg.ssm is not None and cfg.attention is None:
        # pure selective-SSM stack (mamba): every layer is the same block
        return [(("ssm",), L)]

    a = cfg.attention
    if cfg.family == "moe":
        first = cfg.moe.first_dense_layers
        if a.kind == "mla":
            dense_bt, moe_bt = "mla_dense", "mla_moe"
        else:
            dense_bt, moe_bt = "attn_full", "attn_moe"
        groups = []
        if first:
            groups.append(((dense_bt,), first))
        groups.append(((moe_bt,), L - first))
        return groups

    if cfg.parallel_ssm_attn:
        ratio = a.local_global_ratio
        if ratio:
            cyc = ("hybrid_local",) * ratio + ("hybrid_full",)
            n = L // len(cyc)
            groups = [(cyc, n)]
            rem = L - n * len(cyc)
            if rem:
                groups.append((("hybrid_local",) * rem, 1))
            return groups
        return [(("hybrid_full",), L)]

    if a is not None and a.local_global_ratio:
        cyc = ("attn_local",) * a.local_global_ratio + ("attn_full",)
        n = L // len(cyc)
        groups = [(cyc, n)]
        rem = L - n * len(cyc)
        if rem:
            groups.append((("attn_local",) * rem, 1))
        return groups

    return [(("attn_full",), L)]


def flat_block_types(cfg: ModelConfig) -> List[str]:
    out: List[str] = []
    for pattern, r in layer_plan(cfg):
        out.extend(list(pattern) * r)
    return out


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key: jax.Array,
                param_dtype=jnp.float32) -> Tuple[PyTree, PyTree]:
    b = ParamBuilder(key, dtype=param_dtype)
    b.param("embed", (cfg.vocab_size, cfg.d_model), ("vocab", "embed"))
    if cfg.frontend.kind != "none" and cfg.frontend.feature_dim != cfg.d_model:
        b.param("frontend_proj", (cfg.frontend.feature_dim, cfg.d_model),
                (None, "embed"))
    if not cfg.tie_embeddings:
        b.param("head", (cfg.d_model, cfg.vocab_size), ("embed", "vocab"))
    b.param("final_norm", (cfg.d_model,), ("embed",), init="zeros")

    groups_p: List[Any] = []
    groups_a: List[Any] = []
    for g_idx, (pattern, repeats) in enumerate(layer_plan(cfg)):
        pat_p, pat_a = [], []
        for p_idx, bt in enumerate(pattern):
            reps_p = []
            axes_ref = None
            for r in range(repeats):
                bb = ParamBuilder(jax.random.fold_in(key, g_idx * 10000 + p_idx * 100 + r),
                                  dtype=param_dtype)
                blocks_mod.init_block(bb, bt, cfg)
                reps_p.append(bb.params)
                axes_ref = bb.axes
            if repeats > 1:
                stacked = jax.tree.map(lambda *xs: jnp.stack(xs, 0), *reps_p)
                pat_p.append(stacked)
                pat_a.append(stack_axes(axes_ref, "layer"))
            else:
                pat_p.append(reps_p[0])
                pat_a.append(axes_ref)
        groups_p.append(pat_p)
        groups_a.append(pat_a)
    b.params["groups"] = groups_p
    b.axes["groups"] = groups_a
    return b.params, b.axes


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> PyTree:
    """Cache pytree mirroring the group structure + one shared length scalar.

    Sliding-window ("*_local") layers allocate min(window, max_len) slots when
    ``cfg`` enables ring caches (beyond-paper memory optimization; see
    EXPERIMENTS.md §Perf) — baseline allocates full length everywhere.
    """
    groups = []
    for pattern, repeats in layer_plan(cfg):
        pat = []
        for bt in pattern:
            one = blocks_mod.init_block_cache(bt, cfg, batch, max_len, dtype)
            if repeats > 1:
                one = jax.tree.map(
                    lambda x: jnp.broadcast_to(x[None], (repeats,) + x.shape), one)
            pat.append(one)
        groups.append(pat)
    return {"length": jnp.zeros((), jnp.int32), "groups": groups}


def init_paged_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16) -> PyTree:
    """Paged serving cache: one (num_blocks, block_size, K, D) k/v pool per
    layer (MLA: one (num_blocks, block_size, lanes) latent pool), mirroring
    the group structure. One *logical* block id indexes the same slot in
    every layer's pool, so the scheduler tracks a single block table per
    request. No length scalar: per-request lengths live host-side in the
    scheduler (``repro.engine.Engine``). Raises for block types the pool
    cannot hold (``blocks.PAGED_BLOCK_TYPES``).
    """
    groups = []
    for pattern, repeats in layer_plan(cfg):
        pat = []
        for bt in pattern:
            one = blocks_mod.init_paged_block_cache(bt, cfg, num_blocks,
                                                    block_size, dtype)
            if repeats > 1:
                one = jax.tree.map(
                    lambda x: jnp.broadcast_to(x[None], (repeats,) + x.shape), one)
            pat.append(one)
        groups.append(pat)
    return {"groups": groups}


def pool_writes_in_place(pool: PyTree) -> bool:
    """Whether a stacked paged-pool group is written through its flat view.

    Merging the leading (L, N) axes of an (L, N, bs, K, D) leaf, or of an
    MLA latent leaf (L, N, bs, lanes), is free only where the device keeps
    the leaf row-major. The TPU does so when the last two dims fill whole
    (8, 128) tiles (the latent's lanes are padded to 128s, its bs rows are
    the tile's rows); for D=96 or 64 its default layout puts the
    block axis N innermost (Phi-3's pool: ``bf16[32,1017,16,32,96]
    {1,4,3,2,0}``), the flat view becomes a transposing copy of the pool, and
    the per-layer slice path is the cheaper one (PERF.md §5).
    """
    return all(t.shape[-1] % 128 == 0 and t.shape[-2] % 8 == 0
               for t in jax.tree.leaves(pool))


def paged_in_place_layers(cfg: ModelConfig, cache: PyTree) -> Tuple[int, int]:
    """(layers whose pool the paged step writes in place, all layers)."""
    in_place = total = 0
    for (pattern, repeats), group in zip(layer_plan(cfg), cache["groups"]):
        n = repeats * len(pattern)
        total += n
        if pool_writes_in_place(group):
            in_place += n
    return in_place, total


def paged_latent_layers(cfg: ModelConfig, cache: PyTree) -> int:
    """Layers whose paged pool is an MLA latent leaf: the latent kernel's
    calls a step."""
    return sum(repeats
               for (_, repeats), group in zip(layer_plan(cfg), cache["groups"])
               for one in group if "latent" in one)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def forward(
    cfg: ModelConfig,
    params: PyTree,
    tokens: jax.Array,                       # (B, S) int32; audio: unused
    *,
    frontend_feats: Optional[jax.Array] = None,   # audio (B,T,feat) / vlm (B,P,d)
    positions: Optional[jax.Array] = None,
    mrope_positions: Optional[jax.Array] = None,
    cache: Optional[PyTree] = None,
    moe_transport=None,
    compute_dtype=jnp.bfloat16,
    constrain=None,                          # activation sharding constraint
    paged: Optional[PagedLayout] = None,     # serving: block-table cache view
    paged_kernel="auto",         # paged attention: pallas|ref|auto|callable
    recurrent: Optional[RecurrentLayout] = None,  # serving: valid-prefix layout
) -> Tuple[jax.Array, Optional[PyTree], jax.Array]:
    # ``constrain(x)`` pins (B, S, d) activations to the batch sharding at
    # the embedding, between layer groups, and inside the scanned body —
    # without it GSPMD is free to replicate the batch across the dp axis
    # when params are FSDP-sharded (observed: 16x redundant compute and a
    # full-batch logits buffer per chip; see EXPERIMENTS.md §Perf iter 1).
    if constrain is None:
        constrain = lambda t: t
    if cfg.frontend.kind == "audio_frames":
        x = jnp.einsum("btf,fd->btd", frontend_feats.astype(compute_dtype),
                       params["frontend_proj"].astype(compute_dtype))
    else:
        with jax.named_scope("embed"):
            x = params["embed"].astype(compute_dtype)[tokens]
            x = x * jnp.asarray(cfg.d_model ** 0.5, compute_dtype)
        if cfg.frontend.kind == "vision_patches" and frontend_feats is not None:
            # splice precomputed image-patch embeddings over the first P slots
            p = frontend_feats.shape[1]
            x = jax.lax.dynamic_update_slice(
                x, frontend_feats.astype(compute_dtype), (0, 0, 0))
            del p

    x = constrain(x)
    B, S = x.shape[0], x.shape[1]
    if paged is not None:
        # paged cache carries no global length scalar — positions are
        # per-request (starts) and lengths live in the scheduler
        length = None
        positions = paged.token_positions(S)
    elif recurrent is not None:
        # constant-size state: positions are per-request (starts); the
        # shared length scalar is frozen — host-side entry.pos is the truth
        length = cache["length"]
        positions = recurrent.token_positions(S)
    else:
        length = cache["length"] if cache is not None else None
        if positions is None:
            off = length if cache is not None else jnp.int32(0)
            positions = jnp.arange(S, dtype=jnp.int32)[None, :] + off

    plan = layer_plan(cfg)
    new_groups: List[Any] = []
    aux_total = jnp.zeros((), jnp.float32)

    for g_idx, (pattern, repeats) in enumerate(plan):
        # Cast the whole layer stack to the compute dtype BEFORE the scan
        # (§Perf A1/C1): FSDP all-gathers and per-layer weight reads inside
        # the loop then move bf16 — half the ICI and HBM bytes of gathering
        # f32 masters and casting per layer.
        pat_params = jax.tree.map(
            lambda t: t.astype(compute_dtype)
            if t.dtype in (jnp.float32, jnp.bfloat16) else t,
            params["groups"][g_idx])
        pat_cache = cache["groups"][g_idx] if cache is not None else None

        def body(carry, per_layer, pattern=pattern, layout=paged):
            x_c, aux_c = carry
            lp, lc = per_layer
            new_lc = []
            for p_idx, bt in enumerate(pattern):
                c_in = lc[p_idx] if lc is not None else None
                x_c, c_out, aux = blocks_mod.apply_block(
                    bt, lp[p_idx],
                    x_c, cfg, cache=c_in, length=length,
                    positions=positions, mrope_positions=mrope_positions,
                    moe_transport=moe_transport, paged=layout,
                    paged_kernel=paged_kernel, recurrent=recurrent)
                x_c = constrain(x_c)
                new_lc.append(c_out)
            return (x_c, aux_c + aux), new_lc

        # Decode (S==1) unrolls the layer loop: a scanned cache is xs->ys,
        # which double-buffers the FULL per-layer KV cache every step
        # (~170 GiB temps at 32k x B128). Unrolled, each layer's update is
        # DUS(DS(stacked)) — in place on the donated cache buffer. Paged
        # steps always unroll for the same reason: the pool is the dominant
        # buffer and must update in place on the donated argument. Where its
        # layout allows (``pool_writes_in_place``), they scatter into a flat
        # view of the stacked pool instead: DUS(DS(stacked)) still copies
        # each layer's whole pool out and back.
        # Pure-recurrent stacks (xLSTM, SSM-only) always scan: the state is
        # constant-size (double-buffering is cheap) and scan-vs-unroll round
        # differently at 1 bf16 ulp — keeping every path (prefill, S==1
        # decode, masked serving chunks) on the scan is what makes chunked
        # recurrent serving bitwise-identical to the contiguous reference.
        pure_recurrent = all(
            bt in blocks_mod.RECURRENT_BLOCK_TYPES for bt in pattern)
        unroll = cache is not None and (
            paged is not None or (S == 1 and not pure_recurrent))
        if repeats > 1 and unroll and paged is not None and (
                pool_writes_in_place(pat_cache)):
            # one flat (L*N, bs, K, D) view of each stacked pool leaf: layer
            # r addresses its blocks at r*N onward, so the scatter writes
            # the donated buffer itself — no per-layer slice or write-back
            n_blocks = jax.tree.leaves(pat_cache)[0].shape[1]
            with jax.named_scope("kv_pool"):
                flat = jax.tree.map(lambda t: t.reshape(-1, *t.shape[2:]),
                                    pat_cache)
            for r in range(repeats):
                lp = jax.tree.map(lambda t: t[r], pat_params)
                with jax.named_scope("kv_pool"):
                    tables = paged.block_tables
                    layer_paged = paged._replace(block_tables=jnp.where(
                        tables >= 0, tables + r * n_blocks, tables))
                (x, aux_total), flat = body((x, aux_total), (lp, flat),
                                            layout=layer_paged)
            with jax.named_scope("kv_pool"):
                new_pat_cache = jax.tree.map(
                    lambda f, t: f.reshape(t.shape), flat, pat_cache)
        elif repeats > 1 and unroll:
            new_pat_cache = pat_cache
            for r in range(repeats):
                lp = jax.tree.map(lambda t: t[r], pat_params)
                with jax.named_scope("kv_pool"):
                    lc = jax.tree.map(lambda t: t[r], new_pat_cache)
                (x, aux_total), out_lc = body((x, aux_total), (lp, lc))
                with jax.named_scope("kv_pool"):
                    new_pat_cache = jax.tree.map(
                        lambda full, one: full.at[r].set(one),
                        new_pat_cache, out_lc)
        elif repeats > 1:
            scan_body = body
            if cfg.remat == "full":
                scan_body = jax.checkpoint(body)
            (x, aux_total), new_pat_cache = jax.lax.scan(
                scan_body, (x, aux_total), (pat_params, pat_cache))
        else:
            (x, aux_total), new_pat_cache = body((x, aux_total),
                                                 (pat_params, pat_cache))
        new_groups.append(new_pat_cache)

    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"].astype(compute_dtype),
                     cfg.norm_eps)
        if cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", x,
                                params["embed"].astype(compute_dtype))
        else:
            logits = jnp.einsum("bsd,dv->bsv", x,
                                params["head"].astype(compute_dtype))
        logits = softcap(logits.astype(jnp.float32), cfg.final_logit_softcap)

    new_cache = None
    if cache is not None:
        if paged is not None:
            new_cache = {"groups": new_groups}
        elif recurrent is not None:
            # per-request progress is host-side (entry.pos); the shared
            # scalar stays frozen so slot rows never skew
            new_cache = {"length": cache["length"], "groups": new_groups}
        else:
            new_cache = {"length": length + S, "groups": new_groups}
    return logits, new_cache, aux_total


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def loss_fn(cfg: ModelConfig, params: PyTree, batch: Dict[str, jax.Array],
            moe_transport=None, constrain=None
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token CE (decoder) or masked-frame CE (encoder). batch:
    {tokens (B,S), labels (B,S), [features], [mrope_positions]}."""
    logits, _, aux = forward(
        cfg, params, batch["tokens"],
        frontend_feats=batch.get("features"),
        mrope_positions=batch.get("mrope_positions"),
        moe_transport=moe_transport, constrain=constrain)
    labels = batch["labels"]
    if not cfg.is_encoder:
        logits = logits[:, :-1]
        labels = labels[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    n_cls = logits.shape[-1]
    ce = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    mask = (labels >= 0) & (labels < n_cls)
    ce = jnp.where(mask, ce, 0.0)
    denom = jnp.maximum(mask.sum(), 1)
    loss = ce.sum() / denom + aux
    return loss, {"ce": ce.sum() / denom, "aux": aux,
                  "tokens": denom.astype(jnp.float32)}


def decode_step(cfg: ModelConfig, params: PyTree, cache: PyTree,
                token: jax.Array, moe_transport=None,
                mrope_positions: Optional[jax.Array] = None,
                compute_dtype=jnp.bfloat16, constrain=None
                ) -> Tuple[jax.Array, PyTree]:
    """One-token decode. token: (B, 1) int32 -> (logits (B,1,V), new_cache)."""
    logits, new_cache, _ = forward(cfg, params, token, cache=cache,
                                   moe_transport=moe_transport,
                                   mrope_positions=mrope_positions,
                                   compute_dtype=compute_dtype,
                                   constrain=constrain)
    return logits, new_cache
