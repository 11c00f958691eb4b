"""Pure-jnp oracle for the paged-attention kernel: gather-then-dense.

This is the path `models.attention.gqa_paged_attention` shipped with before
the Pallas kernel existed, moved here verbatim so it can serve as (a) the
fp-exact fallback on backends without a usable Pallas lowering and (b) the
differential oracle for `kernel.py`. It materializes each request's logical
``(max_blocks * block_size, K, D)`` KV view in HBM and masks most of it away
— exactly the DRAM bounce the kernel exists to delete (paper §VII-B: the
non-stashed path).

Eager callers get the satellite-3 bound: ``PagedKVCache.gather(seq_lens=)``
returns ``max_resident``, and when it is concrete the logical view is
sliced to the longest live sequence (rounded up to ``block_size``) instead
of always ``max_blocks * block_size``. Under jit the bound is a tracer and
the full fixed-shape view is used (shapes must be static) — that case is
what ``kernel.py`` is for.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.kvcache import PagedKVCache

NEG_INF = -2.0 ** 30  # large-but-finite: keeps fully-masked rows NaN-free


def paged_attention_ref(
    q: jax.Array,                      # (B, C, H, D)
    k_pool: jax.Array,                 # (N_blocks, block_size, K, D)
    v_pool: jax.Array,
    block_tables: jax.Array,           # (B, M) int32, -1 = unallocated
    starts: jax.Array,                 # (B,) int32
    n_valid: jax.Array,                # (B,) int32
    *,
    block_size: int,
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Dense paged attention against the gathered logical view.

    Returns (B, C, H, D) in ``q.dtype``. Columns ``>= n_valid[b]`` produce
    garbage the caller discards (same contract as the kernel).
    """
    B, C, H, D = q.shape
    K = k_pool.shape[2]
    G = H // K
    scale = scale if scale is not None else D ** -0.5

    cache = PagedKVCache(k_pool, v_pool, block_size)
    seq_end = starts + n_valid
    k_all, v_all, max_resident = cache.gather(block_tables, seq_lens=seq_end)
    if not isinstance(max_resident, jax.core.Tracer):
        # eager: bound T to the longest live sequence (block-rounded). Rows
        # with any unmasked position are unchanged — sliced-off columns
        # were NEG_INF, whose exp underflows to exactly 0.0 in f32. A
        # fully-masked row (seq_end == 0) degenerates to a uniform average
        # over however many columns exist, so its garbage depends on T —
        # but such rows are discarded by every caller (the step contract;
        # the kernel returns zeros for them).
        t = max(int(max_resident), block_size)
        k_all, v_all = k_all[:, :t], v_all[:, :t]
    T = k_all.shape[1]

    positions = starts[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    kv_pos = jnp.arange(T, dtype=jnp.int32)
    rel = positions[:, :, None] - kv_pos[None, None, :]      # (B, C, T)
    mask = rel >= 0                                          # causal
    if window is not None:
        mask &= rel < window
    # never read past the tokens resident after this step's writes (keeps
    # stale pool rows from reused blocks out of even discarded columns)
    mask &= kv_pos[None, None, :] < seq_end[:, None, None]
    mask = mask[:, None, None, :, :]                         # (B,1,1,C,T)

    qg = q.reshape(B, C, K, G, D)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k_all.astype(q.dtype),
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(q.dtype),
                     v_all.astype(q.dtype))
    return out.reshape(B, C, H, D)


def paged_latent_attention_ref(
    q: jax.Array,                      # (B, C, H, lanes)
    pool: jax.Array,                   # (N_blocks, block_size, lanes)
    block_tables: jax.Array,           # (B, M) int32, -1 = unallocated
    starts: jax.Array,                 # (B,) int32
    n_valid: jax.Array,                # (B,) int32
    *,
    block_size: int,
    v_lanes: int,
    scale: float,
) -> jax.Array:
    """Dense latent (absorbed MLA) attention against the gathered logical
    view: every head scores against each token's whole latent row and
    averages its first ``v_lanes`` lanes. Returns (B, C, H, v_lanes);
    columns ``>= n_valid[b]`` are garbage the caller discards."""
    B, C, _, lanes = q.shape
    M = block_tables.shape[1]
    rows = (jnp.clip(block_tables, 0)[:, :, None] * block_size
            + jnp.arange(block_size, dtype=jnp.int32)[None, None, :])
    c_all = pool.reshape(-1, lanes)[rows.reshape(B, M * block_size)]
    T = c_all.shape[1]
    positions = starts[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    kv_pos = jnp.arange(T, dtype=jnp.int32)
    mask = ((positions[:, :, None] >= kv_pos[None, None, :])
            & (kv_pos[None, None, :] < (starts + n_valid)[:, None, None]))
    scores = jnp.einsum("bchl,btl->bhct", q, c_all.astype(q.dtype),
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(mask[:, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhct,btv->bchv", probs.astype(q.dtype),
                      c_all[..., :v_lanes].astype(q.dtype))
