"""Attention layers: GQA/MQA/MHA (full, sliding-window, encoder) and MLA.

All score math runs in float32; inputs/outputs stay in the compute dtype.
Decode uses the KV caches from ``kvcache.py``; MLA decode uses the *absorbed*
formulation (scores against the compressed cache — the memory-bound win that
makes MLA decode viable at 32k), and so does paged MLA serving, for prefill
chunks and decode rows alike (``mla_paged_attention``).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import AttentionConfig
from repro.kernels import paged_attention as paged_kernel
from repro.models.common import ParamBuilder, rms_norm
from repro.models.kvcache import KVCache, MLACache, PagedKVCache, PagedLayout
from repro.models.rope import apply_mrope, apply_rope, yarn_mscale

NEG_INF = -2.0 ** 30  # large-but-finite: keeps fully-masked rows NaN-free


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

def init_gqa(b: ParamBuilder, d_model: int, a: AttentionConfig) -> None:
    b.param("wq", (d_model, a.num_heads, a.head_dim), ("embed", "heads", "head_dim"))
    b.param("wk", (d_model, a.num_kv_heads, a.head_dim), ("embed", "kv_heads", "head_dim"))
    b.param("wv", (d_model, a.num_kv_heads, a.head_dim), ("embed", "kv_heads", "head_dim"))
    b.param("wo", (a.num_heads, a.head_dim, d_model), ("heads", "head_dim", "embed"),
            fan_in=a.num_heads * a.head_dim)


def init_mla(b: ParamBuilder, d_model: int, a: AttentionConfig) -> None:
    qk_head = a.qk_nope_head_dim + a.qk_rope_head_dim
    b.param("wq", (d_model, a.num_heads, qk_head), ("embed", "heads", "head_dim"))
    b.param("w_dkv", (d_model, a.kv_lora_rank + a.qk_rope_head_dim), ("embed", "kv_lora"))
    b.param("kv_norm", (a.kv_lora_rank,), ("kv_lora",), init="zeros")
    b.param("w_uk", (a.kv_lora_rank, a.num_heads, a.qk_nope_head_dim),
            ("kv_lora", "heads", "head_dim"))
    b.param("w_uv", (a.kv_lora_rank, a.num_heads, a.v_head_dim),
            ("kv_lora", "heads", "head_dim"))
    b.param("wo", (a.num_heads, a.v_head_dim, d_model), ("heads", "head_dim", "embed"),
            fan_in=a.num_heads * a.v_head_dim)


# ---------------------------------------------------------------------------
# Masking
# ---------------------------------------------------------------------------

def make_mask(q_len: int, kv_len: int, *, causal: bool,
              window: Optional[int] = None,
              q_offset: Optional[jax.Array] = None) -> jax.Array:
    """(q_len, kv_len) boolean mask. ``q_offset``: absolute position of q[0]."""
    q_pos = jnp.arange(q_len)
    if q_offset is not None:
        q_pos = q_pos + q_offset
    k_pos = jnp.arange(kv_len)
    rel = q_pos[:, None] - k_pos[None, :]
    mask = jnp.ones((q_len, kv_len), bool)
    if causal:
        mask &= rel >= 0
    if window is not None:
        mask &= rel < window
    return mask


def _sdpa(q: jax.Array, k: jax.Array, v: jax.Array, mask: Optional[jax.Array],
          scale: float) -> jax.Array:
    """q: (B,S,K,G,D) grouped; k,v: (B,T,K,D). Returns (B,S,K,G,D_v)."""
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k, preferred_element_type=jnp.float32)
    scores = scores * scale
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(v.dtype), v)
    return out


# Chunking policy: sequences whose (S x T) score matrix would exceed this many
# elements per (batch, head) take the blockwise online-softmax path. This is
# the pure-jnp flash-attention formulation (also the oracle for the Pallas
# kernel in kernels/flash_attention).
CHUNK_THRESHOLD = 1 << 22
Q_CHUNK = 1024
KV_CHUNK = 1024
# Beyond-paper opt (§Perf): skip kv blocks that are fully masked for a q block
# (causal upper triangle / outside the sliding window). Python-unrolled over q
# blocks, so HLO grows O(n_q_blocks); enabled per-run by the perf configs.
BLOCK_SKIP = False


def _use_chunked(s: int, t: int) -> bool:
    return s > 1 and s * t > CHUNK_THRESHOLD


def _chunk_of(n: int, want: int) -> int:
    c = min(want, n)
    while n % c:
        c -= 1
    return c


def _sdpa_chunked(q: jax.Array, k: jax.Array, v: jax.Array, *, scale: float,
                  q_pos: jax.Array, kv_pos: jax.Array, causal: bool,
                  window: Optional[int], canonical_positions: bool = False,
                  q_chunk: int = 0, kv_chunk: int = 0) -> jax.Array:
    """Blockwise online-softmax attention (flash formulation, pure jnp).

    q: (B,S,K,G,D); k,v: (B,T,K,Dk/Dv); q_pos: (B,S); kv_pos: (B,T).
    Peak memory is O(q_chunk x kv_chunk) scores per (B,K,G) instead of SxT.
    The kv loop is a ``lax.scan`` with a checkpointed body, so the backward
    pass recomputes block scores (flash-bwd) instead of storing them.
    """
    B, S, K, G, D = q.shape
    T = k.shape[1]
    Dv = v.shape[-1]
    q_pos = jnp.broadcast_to(q_pos, (B, S))
    kv_pos = jnp.broadcast_to(kv_pos, (B, T))
    qc = _chunk_of(S, q_chunk or Q_CHUNK)
    kc = _chunk_of(T, kv_chunk or KV_CHUNK)
    nq, nk = S // qc, T // kc

    q_r = jnp.moveaxis(q.reshape(B, nq, qc, K, G, D), 1, 0)
    qp_r = jnp.moveaxis(q_pos.reshape(B, nq, qc), 1, 0)
    k_r = jnp.moveaxis(k.reshape(B, nk, kc, K, D), 1, 0)
    v_r = jnp.moveaxis(v.reshape(B, nk, kc, K, Dv), 1, 0)
    kp_r = jnp.moveaxis(kv_pos.reshape(B, nk, kc), 1, 0)

    def block(qb, qpb, kb, vb, kpb, carry):
        m, l, acc = carry
        s = jnp.einsum("bqkgd,btkd->bkgqt", qb, kb,
                       preferred_element_type=jnp.float32) * scale
        rel = (qpb[:, None, None, :, None].astype(jnp.int32)
               - kpb[:, None, None, None, :].astype(jnp.int32))
        mask = jnp.ones(rel.shape, bool)
        if causal:
            mask &= rel >= 0
        if window is not None:
            mask &= rel < window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(-1)
        acc_new = (acc * alpha[..., None]
                   + jnp.einsum("bkgqt,btkd->bkgqd", p.astype(vb.dtype), vb)
                   .astype(jnp.float32))
        return m_new, l_new, acc_new

    block_ck = jax.checkpoint(block)

    def init_carry():
        return (jnp.full((B, K, G, qc), -1e30, jnp.float32),
                jnp.zeros((B, K, G, qc), jnp.float32),
                jnp.zeros((B, K, G, qc, Dv), jnp.float32))

    def finish(carry):
        m, l, acc = carry
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return jnp.moveaxis(out, 3, 1)           # (B,qc,K,G,Dv)

    if BLOCK_SKIP and canonical_positions:
        # Positions are the canonical arange from 0, so each (q block, kv
        # block) pair's visibility is static: skip kv blocks entirely above
        # the causal diagonal or entirely outside the sliding window. This
        # removes the ~2x causal waste (and ~T/window for local layers) from
        # the compute roofline term at the cost of O(nq) HLO body clones.
        outs = []
        for i in range(nq):
            lo, hi = i * qc, i * qc + qc - 1     # absolute q range
            carry = init_carry()
            for j in range(nk):
                k_lo, k_hi = j * kc, j * kc + kc - 1
                if causal and k_lo > hi:
                    continue                      # above the diagonal
                if window is not None and k_hi < lo - window + 1:
                    continue                      # before the window
                carry = block_ck(q_r[i], qp_r[i], k_r[j], v_r[j], kp_r[j],
                                 carry)
            outs.append(finish(carry))
        out = jnp.concatenate(outs, axis=1)       # (B,S,K,G,Dv)
        return out.astype(q.dtype)

    def per_q(args):
        qb, qpb = args

        def body(carry, inp):
            kb, vb, kpb = inp
            return block_ck(qb, qpb, kb, vb, kpb, carry), None

        carry, _ = jax.lax.scan(body, init_carry(), (k_r, v_r, kp_r))
        return finish(carry)

    out = jax.lax.map(per_q, (q_r, qp_r))        # (nq,B,qc,K,G,Dv)
    out = jnp.moveaxis(out, 0, 1).reshape(B, S, K, G, Dv)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA forward
# ---------------------------------------------------------------------------

def gqa_attention(
    params,
    x: jax.Array,                          # (B, S, d)
    a: AttentionConfig,
    *,
    causal: bool = True,
    window: Optional[int] = None,          # sliding window (None = full)
    cache: Optional[KVCache] = None,       # decode/prefill-with-cache
    positions: Optional[jax.Array] = None, # (B, S) absolute positions
    mrope_positions: Optional[jax.Array] = None,  # (3, B, S)
) -> Tuple[jax.Array, Optional[KVCache]]:
    B, S, _ = x.shape
    H, K, D = a.num_heads, a.num_kv_heads, a.head_dim
    G = H // K
    if positions is None:
        offset = cache.length if cache is not None else jnp.int32(0)
        positions = jnp.arange(S, dtype=jnp.int32)[None, :] + offset

    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])        # (B,S,H,D)
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])        # (B,S,K,D)
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])

    if a.mrope:
        mpos = mrope_positions
        if mpos is None:
            mpos = jnp.broadcast_to(positions[None], (3, B, S))
        q = apply_mrope(q, mpos, a.rope_theta, a.mrope_sections)
        k = apply_mrope(k, mpos, a.rope_theta, a.mrope_sections)
    elif a.rotary_pct > 0:
        q = apply_rope(q, positions, a.rope_theta, a.rotary_pct)
        k = apply_rope(k, positions, a.rope_theta, a.rotary_pct)

    qg = q.reshape(B, S, K, G, D)
    scale = 1.0 / math.sqrt(D)

    new_cache = None
    if cache is not None and S > 1:
        # Prefill into a fresh cache: attend over the in-block k/v (identical
        # result, avoids touching max_len empty slots), then append. Same
        # chunking policy as the no-cache branch — short prefills use the
        # plain softmax so cached and cacheless forward stay bitwise
        # consistent (greedy serving depends on that identity).
        new_cache = cache.append(k, v)
        if _use_chunked(S, S):
            out = _sdpa_chunked(qg, k.astype(x.dtype), v.astype(x.dtype),
                                scale=scale, q_pos=positions,
                                kv_pos=positions, causal=causal,
                                window=window, canonical_positions=True)
        else:
            mask = None
            if causal or window is not None:
                mask = make_mask(S, S, causal=causal,
                                 window=window)[None, None, None]
            out = _sdpa(qg, k.astype(x.dtype), v.astype(x.dtype), mask,
                        scale)
    elif cache is not None:
        # Decode: dense scores over the cache (S==1: scores are (B,K,G,1,T)).
        new_cache = cache.append(k, v)
        k_all, v_all = new_cache.k, new_cache.v
        T = new_cache.max_len
        kv_pos = jnp.arange(T)
        rel = positions[:, :, None] - kv_pos[None, None, :]    # (B,S,T)
        mask = rel >= 0
        if window is not None:
            mask &= rel < window
        mask = mask[:, None, None, :, :]                       # (B,1,1,S,T)
        out = _sdpa(qg, k_all.astype(x.dtype), v_all.astype(x.dtype), mask,
                    scale)
    elif _use_chunked(S, S):
        out = _sdpa_chunked(qg, k.astype(x.dtype), v.astype(x.dtype),
                            scale=scale, q_pos=positions,
                            kv_pos=positions, causal=causal, window=window,
                            canonical_positions=True)
    else:
        mask = None
        if causal or window is not None:
            mask = make_mask(S, S, causal=causal, window=window)[None, None, None]
        out = _sdpa(qg, k.astype(x.dtype), v.astype(x.dtype), mask, scale)

    out = out.reshape(B, S, H, D)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return y, new_cache


# ---------------------------------------------------------------------------
# Paged GQA forward (serving: block-table cache, decode + chunked prefill)
# ---------------------------------------------------------------------------

def gqa_paged_attention(
    params,
    x: jax.Array,                          # (B, C, d): C-token chunk per slot
    a: AttentionConfig,
    *,
    cache: PagedKVCache,
    layout: PagedLayout,
    window: Optional[int] = None,
    kernel="auto",
) -> Tuple[jax.Array, PagedKVCache]:
    """One serving step through a paged cache.

    Each batch row is one request slot advancing ``n_valid`` tokens whose
    absolute positions start at ``starts`` — decode rows advance 1 token,
    chunked-prefill rows up to C, idle rows 0. New k/v scatter into the
    shared pool through the block table; scores then run either through the
    stash-resident Pallas kernel (``kernel="pallas"`` — live blocks stream
    pool->VMEM, the logical view never exists in HBM) or the gather-then-
    dense oracle (``kernel="ref"``). ``"auto"`` picks pallas
    (``kernels.paged_attention.resolve_kernel``).
    ``kernel`` may also be a *callable* with ``paged_attention_ref``'s
    signature — that is how ``runtime.steps`` threads the shard_map'd
    multi-device lowering (``make_sharded_paged_attention``) down here
    without the model layer knowing about meshes.
    Columns beyond ``n_valid`` produce garbage outputs that the caller
    discards (their cache writes are dropped), which is what lets decode and
    prefill share one compiled shape — the ISSUE-2 "decode-shaped step, no
    per-bucket prefill jits".
    """
    assert not a.mrope, "paged serving does not support mrope archs yet"
    B, C, _ = x.shape
    H, K, D = a.num_heads, a.num_kv_heads, a.head_dim
    positions = layout.token_positions(C)                   # (B, C)

    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if a.rotary_pct > 0:
        q = apply_rope(q, positions, a.rope_theta, a.rotary_pct)
        k = apply_rope(k, positions, a.rope_theta, a.rotary_pct)

    with jax.named_scope("kv_pool"):
        new_cache = cache.write(k, v, layout)
    if callable(kernel):
        fn = kernel
    else:
        kind = paged_kernel.resolve_kernel(kernel)
        fn = (paged_kernel.paged_attention if kind == "pallas"
              else paged_kernel.paged_attention_ref)
    out = fn(q.astype(x.dtype), new_cache.k_pool, new_cache.v_pool,
             layout.block_tables, layout.starts, layout.n_valid,
             block_size=layout.block_size, window=window,
             scale=1.0 / math.sqrt(D))
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return y, new_cache


# ---------------------------------------------------------------------------
# MLA forward (deepseek-v2)
# ---------------------------------------------------------------------------

def mla_softmax_scale(a: AttentionConfig) -> float:
    """``1 / sqrt(qk_nope + qk_rope)``, times YaRN's ``mscale(mscale_all_dim)``
    squared where the config scales its rotary."""
    scale = 1.0 / math.sqrt(a.qk_nope_head_dim + a.qk_rope_head_dim)
    if a.yarn is not None and a.yarn.mscale_all_dim:
        scale *= yarn_mscale(a.yarn.factor, a.yarn.mscale_all_dim) ** 2
    return scale


def mla_latent_lanes(a: AttentionConfig) -> int:
    """Lanes of one token's latent row in the paged pool: the compressed kv
    and the rotary key, zero-padded to whole 128-lane tiles (so the stacked
    pool is written in place, ``model.pool_writes_in_place``)."""
    return -(-(a.kv_lora_rank + a.qk_rope_head_dim) // 128) * 128


def _mla_project(params, x: jax.Array, a: AttentionConfig,
                 positions: jax.Array, norm_eps: float):
    """q_nope (B,S,H,dn), rotated q_rope (B,S,H,dr), normed c_kv (B,S,r)
    and the rotated shared k_rope (B,S,dr) of x (B,S,d)."""
    dn, r = a.qk_nope_head_dim, a.kv_lora_rank
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])         # (B,S,H,dn+dr)
    q_nope = q[..., :dn]
    q_rope = apply_rope(q[..., dn:], positions, a.rope_theta, yarn=a.yarn)
    ckr = jnp.einsum("bsd,dr->bsr", x, params["w_dkv"])      # (B,S,r+dr)
    c_kv = rms_norm(ckr[..., :r], params["kv_norm"], norm_eps)
    k_rope = apply_rope(ckr[..., None, r:], positions, a.rope_theta,
                        yarn=a.yarn)[:, :, 0]
    return q_nope, q_rope, c_kv, k_rope


def mla_paged_attention(
    params,
    x: jax.Array,                          # (B, C, d): C-token chunk per slot
    a: AttentionConfig,
    *,
    pool: jax.Array,                       # (N_blocks, block_size, lanes)
    layout: PagedLayout,
    kernel: str = "auto",
    norm_eps: float = 1e-6,
) -> Tuple[jax.Array, jax.Array]:
    """One serving step of absorbed MLA through a paged latent pool.

    Each token's latent row ``[c_kv | k_rope | 0]`` scatters into the pool
    (scope ``kv_pool``); each head's query is absorbed into the latent,
    ``[q_nope . W_UK | q_rope | 0]``, so the kernel (``"pallas"``: the
    latent kernel; ``"ref"``: gather-then-dense) scores it against the
    rows and averages their compressed kv; ``W_UV`` per head and ``wo``
    finish. Prefill chunks and decode rows take the same path. Returns
    (y (B, C, d), the new pool).
    """
    B, C, _ = x.shape
    H, r = a.num_heads, a.kv_lora_rank
    positions = layout.token_positions(C)
    q_nope, q_rope, c_kv, k_rope = _mla_project(params, x, a, positions,
                                                norm_eps)
    lanes = pool.shape[-1]
    pad = lanes - r - a.qk_rope_head_dim
    with jax.named_scope("kv_pool"):
        row = jnp.concatenate(
            [c_kv, k_rope, jnp.zeros((B, C, pad), x.dtype)], axis=-1)
        pool = layout.write(pool, row)
    q_abs = jnp.einsum("bshn,rhn->bshr", q_nope, params["w_uk"])
    q_lat = jnp.concatenate(
        [q_abs, q_rope.astype(x.dtype), jnp.zeros((B, C, H, pad), x.dtype)],
        axis=-1)
    fn = (paged_kernel.paged_latent_attention
          if paged_kernel.resolve_kernel(kernel) == "pallas"
          else paged_kernel.paged_latent_attention_ref)
    ctx = fn(q_lat.astype(x.dtype), pool, layout.block_tables, layout.starts,
             layout.n_valid, block_size=layout.block_size, v_lanes=r,
             scale=mla_softmax_scale(a))
    out = jnp.einsum("bshr,rhv->bshv", ctx, params["w_uv"])
    y = jnp.einsum("bshv,hvd->bsd", out, params["wo"])
    return y, pool


def mla_attention(
    params,
    x: jax.Array,
    a: AttentionConfig,
    *,
    causal: bool = True,
    cache: Optional[MLACache] = None,
    positions: Optional[jax.Array] = None,
    norm_eps: float = 1e-6,
) -> Tuple[jax.Array, Optional[MLACache]]:
    B, S, _ = x.shape
    H, dr = a.num_heads, a.qk_rope_head_dim
    if positions is None:
        offset = cache.length if cache is not None else jnp.int32(0)
        positions = jnp.arange(S, dtype=jnp.int32)[None, :] + offset

    q_nope, q_rope, c_kv, k_rope = _mla_project(params, x, a, positions,
                                                norm_eps)
    scale = mla_softmax_scale(a)
    new_cache = None
    if cache is not None and S == 1:
        # Absorbed decode: score against the compressed cache directly —
        # the memory-bound win that makes MLA decode viable at 32k.
        new_cache = cache.append(c_kv, k_rope)
        c_all, kr_all = new_cache.c_kv.astype(x.dtype), new_cache.k_rope.astype(x.dtype)
        T = new_cache.max_len
        rel = positions[:, :, None] - jnp.arange(T)[None, None, :]
        mask = (rel >= 0)[:, None, :, :]                     # (B,1,S,T)
        # absorbed scores: q_nope (B,S,H,dn) @ w_uk -> (B,S,H,r) then vs c_kv
        q_abs = jnp.einsum("bshn,rhn->bshr", q_nope, params["w_uk"])
        scores = jnp.einsum("bshr,btr->bhst", q_abs, c_all,
                            preferred_element_type=jnp.float32)
        scores += jnp.einsum("bshr,btr->bhst", q_rope, kr_all,
                             preferred_element_type=jnp.float32)
        probs = jax.nn.softmax(jnp.where(mask, scores * scale, NEG_INF)
                               .astype(jnp.float32), axis=-1)
        ctx = jnp.einsum("bhst,btr->bshr", probs.astype(x.dtype), c_all)
        out = jnp.einsum("bshr,rhv->bshv", ctx, params["w_uv"])
    else:
        # Train, or prefill into a fresh cache: full-rank in-block attention.
        if cache is not None:
            new_cache = cache.append(c_kv, k_rope)
        k_nope = jnp.einsum("bsr,rhn->bshn", c_kv, params["w_uk"])
        v = jnp.einsum("bsr,rhv->bshv", c_kv, params["w_uv"])
        if _use_chunked(S, S):
            # concat trick: [q_nope|q_rope] . [k_nope|k_rope] in one product
            q_cat = jnp.concatenate(
                [q_nope, q_rope], axis=-1)[:, :, :, None, :]  # (B,S,K=H,G=1,dn+dr)
            k_cat = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                          (B, S, H, dr))], axis=-1)
            out = _sdpa_chunked(q_cat, k_cat, v, scale=scale,
                                q_pos=positions, kv_pos=positions,
                                causal=causal, window=None,
                                canonical_positions=True)[:, :, :, 0, :]
        else:
            scores = jnp.einsum("bshn,bthn->bhst", q_nope, k_nope,
                                preferred_element_type=jnp.float32)
            scores += jnp.einsum("bshr,btr->bhst", q_rope, k_rope,
                                 preferred_element_type=jnp.float32)
            if causal:
                mask = make_mask(S, S, causal=True)[None, None]
                scores = jnp.where(mask, scores * scale, NEG_INF)
            else:
                scores = scores * scale
            probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
            out = jnp.einsum("bhst,bthv->bshv", probs.astype(x.dtype), v)

    y = jnp.einsum("bshv,hvd->bsd", out, params["wo"])
    return y, new_cache
