"""Flash-attention Pallas TPU kernel (32k-prefill compute hot-spot).

Online-softmax blockwise attention with GQA, causal, and sliding-window
masking. The kv dimension is the innermost *arbitrary* grid axis so the
(m, l, acc) running statistics live in VMEM scratch across kv steps — the
score matrix never exists in HBM (the flash formulation; also the "stash"
structure of the Two-Chains mailbox: tiles are consumed where they land).

Grid: ``(B, Hq, S/bq, T/bk)``.

BlockSpecs:
  q   (1, 1, bq, D) per (b, h, i, ·)
  k   (1, 1, bk, D) per (b, h//G, ·, j)   — GQA: G query heads share one kv head
  v   (1, 1, bk, D) per (b, h//G, ·, j)
  out (1, 1, bq, D) per (b, h, i, ·)      — written at the last kv step
  scratch: m (bq, 1) f32, l (bq, 1) f32, acc (bq, D) f32

Fully-masked kv blocks (above the causal diagonal / outside the sliding
window) are skipped with ``pl.when`` — the §Perf BLOCK_SKIP optimization,
done in-kernel where it costs nothing in HLO size.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu
import jax.numpy as jnp

NEG_INF = -2.0 ** 30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: Optional[int],
                  q_offset: int, bq: int, bk: int):
    i = pl.program_id(2)
    j = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Static-per-block visibility: absolute q rows [q_lo, q_hi], kv cols
    # [k_lo, k_hi]. A kv block is skipped when *no* (q, k) pair is visible.
    q_lo = i * bq + q_offset
    q_hi = q_lo + bq - 1
    k_lo = j * bk
    k_hi = k_lo + bk - 1
    visible = jnp.asarray(True)
    if causal:
        visible = jnp.logical_and(visible, k_lo <= q_hi)
    if window is not None:
        visible = jnp.logical_and(visible, k_hi >= q_lo - window + 1)

    @pl.when(visible)
    def _block():
        q = q_ref[0, 0]                               # (bq, D)
        k = k_ref[0, 0]                               # (bk, D)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # (bq, bk)

        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        rel = q_pos - k_pos
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= rel >= 0
        if window is not None:
            mask &= rel < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                            # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)                         # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)                # (bq, 1)
        l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = (acc_ref[...] * alpha
                        + jax.lax.dot_general(
                            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))

    @pl.when(j == nk - 1)
    def _flush():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True, window: Optional[int] = None, q_offset: int = 0,
    scale: Optional[float] = None, block_q: int = 512, block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, Hq, S, D); k, v: (B, Hkv, T, D) -> (B, Hq, S, D)."""
    b, hq, s, d = q.shape
    hkv, t = k.shape[1], k.shape[2]
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    bq = min(block_q, s)
    while s % bq:
        bq -= 1
    bk = min(block_k, t)
    while t % bk:
        bk -= 1

    grid = (b, hq, s // bq, t // bk)
    return pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          window=window, q_offset=q_offset, bq=bq, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i_, j_: (b_, h_, i_, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, i_, j_: (b_, h_ // g, j_, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, i_, j_: (b_, h_ // g, j_, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda b_, h_, i_, j_: (b_, h_, i_, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
