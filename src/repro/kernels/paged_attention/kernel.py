"""Stash-resident paged-attention Pallas TPU kernel (serving hot path).

Flash-style online-softmax attention that walks each request's block table
and streams only *live* KV blocks from the HBM pool into VMEM — the logical
``(max_blocks * block_size, K, D)`` view is never materialized. This is the
TPU analogue of the paper's §VII-B stash path: injected state (the KV pool)
is consumed in cache-adjacent fast memory (VMEM) where it lands, instead of
bouncing through a dense DRAM copy first (which is what ``ref.py`` does).

Grid: ``(B, M)`` — request slot x kv block, kv innermost and *arbitrary*
so the per-head (m, l, acc) running statistics live in VMEM scratch across
kv steps. One grid step fetches a whole pool block, all K kv heads, and
loops over the heads in the body.

Operands (``PrefetchScalarGridSpec``, scalars prefetched to SMEM so the
DMA engine can compute pool addresses before the body runs):
  scalar  block_tables (B, M) int32   pool block ids, -1 = unallocated
  scalar  starts       (B,)  int32    absolute position of column 0
  scalar  seq_end      (B,)  int32    tokens resident after this step
  q   (1, K, G*C, D) per (b, ·)       all C chunk columns x H heads
  k   (1, bs, K, D)  per (·, j)       pool block ``tables[b, min(j, last)]``
  v   (1, bs, K, D)  same
  out (1, K, G*C, D) per (b, ·)       written at the last kv step
  scratch: m (K, G*C, 1) f32, l (K, G*C, 1) f32, acc (K, G*C, D) f32

Block layout: the TPU lowering needs the last two block dims divisible by
(8, 128) or equal to the array's. A per-head block ``(1, bs, 1, D)`` over
the ``(N, bs, K, D)`` pool breaks that (1 against K), so the kernel reads
``(1, bs, K, D)``: one contiguous ``bs * K * D`` DMA per block for k and
one for v (16 KiB each in bf16 at bs=16, K=8, D=64). A head-major pool
``(N, K, bs, D)`` with ``(1, 1, bs, D)`` blocks reads the same bytes as K
DMAs of ``bs * D`` each, but would change the pool layout for every writer,
reader and sharding rule of the cache.

Early exit: the kv index map clamps ``j`` to the request's last live block
(``ceil(seq_end / bs) - 1``), so dead grid steps re-address the block the
pipeline just fetched — Pallas skips the copy when consecutive steps map to
the same block — and ``pl.when`` skips their compute. Work therefore scales
with resident tokens, not pool capacity: one fixed compiled shape serves
decode rows (``n_valid == 1``), chunked-prefill rows (``n_valid <= C``),
and idle rows (``n_valid == 0``, which touch zero blocks).

Latent variant (``paged_latent_attention_pallas``, absorbed MLA): MQA over a
``(N, bs, lanes)`` pool of latent rows, one shared "head" that is the key
over all its lanes and the value over its first ``v_lanes``. Its grid is
``(B, ceil(M / P))``: a grid step covers P table entries of a row
(``latent_blocks_per_step``, 16 at bs=16: 256 tokens), so each step's
products are ``(H*C, lanes) x (lanes, P*bs)`` and ``(H*C, P*bs) x (P*bs,
v_lanes)`` rather than a 16-wide sliver of the MXU. The pool stays in HBM
(``memory_space=pl.ANY``); the body gathers the step's blocks itself, one
DMA of ``(bs, lanes)`` a live allocated entry, into one of two VMEM
buffers ``(2, P, bs, lanes)``. Before it waits on its own copies, each live
step starts those of the next live step into the other buffer: the row's
next step, else the first step of the next row with tokens, so the
prefetch crosses rows and both grid axes run in order. Dead steps (past
``ceil(seq_end / bs)`` blocks) start and compute nothing; entries past M
or of -1 are never copied, and their columns are masked.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu
import jax.numpy as jnp

NEG_INF = -2.0 ** 30


def _paged_kernel(tables_ref, starts_ref, seq_end_ref, q_ref, k_ref, v_ref,
                  o_ref, m_ref, l_ref, acc_ref, *, bs: int, chunk: int,
                  window: Optional[int], scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    kv_heads, gc = q_ref.shape[1], q_ref.shape[2]   # K, G * C rows

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start = starts_ref[b]
    seq_end = seq_end_ref[b]
    n_live = (seq_end + bs - 1) // bs             # live kv blocks this row
    visible = j < n_live
    if window is not None:
        # the whole block precedes every query's window: skip it. The
        # earliest visible kv position for column 0 is start - window + 1.
        visible = jnp.logical_and(visible, j * bs + bs - 1 >= start - (window - 1))

    @pl.when(visible)
    def _block():
        # row r = g * C + c serves chunk column c = r % C of group head g;
        # the mask is the same for every kv head
        q_pos = start + jax.lax.rem(
            jax.lax.broadcasted_iota(jnp.int32, (gc, bs), 0), chunk)
        k_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (gc, bs), 1)
        rel = q_pos - k_pos
        mask = rel >= 0                           # causal
        if window is not None:
            mask &= rel < window
        mask &= k_pos < seq_end                   # stale rows of reused blocks

        for h in range(kv_heads):                 # static: K is small
            q = q_ref[0, h]                       # (G*C, D)
            k = k_ref[0, :, h, :]                 # (bs, D)
            v = v_ref[0, :, h, :]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (G*C, bs)
            s = jnp.where(mask, s, NEG_INF)

            m_prev = m_ref[h]                     # (G*C, 1)
            m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + p.sum(-1, keepdims=True)
            m_ref[h] = m_new
            acc_ref[h] = (acc_ref[h] * alpha
                          + jax.lax.dot_general(
                              p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32))

    @pl.when(j == nj - 1)
    def _flush():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_attention_pallas(
    q: jax.Array,                      # (B, C, H, D)
    k_pool: jax.Array,                 # (N_blocks, block_size, K, D)
    v_pool: jax.Array,
    block_tables: jax.Array,           # (B, M) int32
    starts: jax.Array,                 # (B,) int32
    n_valid: jax.Array,                # (B,) int32
    *,
    block_size: int,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    interpret=False,
) -> jax.Array:
    """Paged attention through the block table. Returns (B, C, H, D)."""
    B, C, H, D = q.shape
    bs = block_size
    K = k_pool.shape[2]
    assert k_pool.shape[1] == bs, (k_pool.shape, bs)
    assert H % K == 0, (H, K)
    G = H // K
    M = block_tables.shape[1]
    scale = scale if scale is not None else D ** -0.5

    # (B, C, K, G, D) -> (B, K, G*C, D): one q tile per (request, kv head)
    qg = q.reshape(B, C, K, G, D).transpose(0, 2, 3, 1, 4).reshape(B, K, G * C, D)
    tables = block_tables.astype(jnp.int32)
    starts = starts.astype(jnp.int32)
    seq_end = starts + n_valid.astype(jnp.int32)

    def q_map(b, j, tables, starts, seq_end):
        return (b, 0, 0, 0)

    def kv_map(b, j, tables, starts, seq_end):
        # clamp dead steps to the nearest live block (same address => the
        # pipeline skips the copy) and unallocated slots (-1) to block 0
        # (their positions are >= seq_end, masked in-kernel). Dead means
        # past the resident tokens (j > last) or, on sliding-window layers,
        # entirely before the earliest visible position (j < lo) — without
        # the lower clamp every live block would still be DMA'd on windowed
        # layers even though its compute is skipped.
        last = jnp.maximum((seq_end[b] + bs - 1) // bs - 1, 0)
        lo = 0
        if window is not None:
            lo = jnp.clip((starts[b] - (window - 1)) // bs, 0, last)
        blk = tables[b, jnp.clip(j, lo, last)]
        return (jnp.maximum(blk, 0), 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, M),
        in_specs=[
            pl.BlockSpec((1, K, G * C, D), q_map),
            pl.BlockSpec((1, bs, K, D), kv_map),
            pl.BlockSpec((1, bs, K, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, K, G * C, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((K, G * C, 1), jnp.float32),
            pltpu.VMEM((K, G * C, 1), jnp.float32),
            pltpu.VMEM((K, G * C, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, bs=bs, chunk=C, window=window,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G * C, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_attention",
    )(tables, starts, seq_end, qg, k_pool, v_pool)
    return (out.reshape(B, K, G, C, D).transpose(0, 3, 1, 2, 4)
            .reshape(B, C, H, D))


# ---------------------------------------------------------------------------
# latent variant (MLA): one shared latent "head" serves as k and v
# ---------------------------------------------------------------------------

# Pool tokens one grid step of the latent kernel gathers: both products
# then span two 128-wide MXU tiles, and the double buffer holds ~640 KiB of
# VMEM at 640 lanes in bf16. 128 and 512 are untimed (PERF.md §6).
LATENT_STEP_TOKENS = 256


def latent_blocks_per_step(max_blocks: int, block_size: int) -> int:
    """P, the block-table entries one grid step of the latent kernel
    covers: ``LATENT_STEP_TOKENS`` worth of blocks, at most ``max_blocks``."""
    return max(1, min(LATENT_STEP_TOKENS // block_size, max_blocks))


def _latent_kernel(tables_ref, starts_ref, seq_end_ref, q_ref, pool_ref,
                   o_ref, m_ref, l_ref, acc_ref, buf_ref, sem_ref, dma_ref,
                   *, bs: int, chunk: int, per_step: int, v_lanes: int,
                   scale: float):
    b = pl.program_id(0)
    i = pl.program_id(1)
    ni = pl.num_programs(1)
    rows, max_blocks = tables_ref.shape
    hc = q_ref.shape[1]                           # H * C query rows
    P = per_step

    def n_live(row):                              # blocks resident after the step
        return (seq_end_ref[row] + bs - 1) // bs

    def entry(row, j):
        # table entry j of the row, and whether a copy fetches it: live and
        # allocated, so never an entry past M nor a -1
        blk = tables_ref[row, jnp.minimum(j, max_blocks - 1)]
        return blk, (j < n_live(row)) & (blk >= 0)

    def copies(row, step, slot, op):
        # start (or wait for) grid step (row, step)'s copies into buffer
        # `slot`: one DMA of bs latent rows a fetched entry
        for k in range(P):
            blk, fetched = entry(row, step * P + k)
            copy = pltpu.make_async_copy(pool_ref.at[jnp.maximum(blk, 0)],
                                         buf_ref.at[slot, k],
                                         sem_ref.at[slot, k])
            pl.when(fetched)(getattr(copy, op))

    @pl.when((b == 0) & (i == 0))
    def _first():
        dma_ref[0] = 0                            # buffer of the next live step
        dma_ref[1] = 0                            # whether its copies started
        # slots no copy fills are masked, but take p = 0 into p·c: zeros
        # keep uninitialised VMEM (a NaN) out of the accumulator
        buf_ref[...] = jnp.zeros_like(buf_ref)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i * P < n_live(b))                   # dead steps do nothing
    def _step():
        slot = dma_ref[0]

        @pl.when(dma_ref[1] == 0)                 # the call's first live step
        def _():
            copies(b, i, slot, "start")

        # start the next live step's copies into the other buffer: this
        # row's next step, else the first step of the next row with tokens
        dma_ref[0] = 1 - slot
        dma_ref[1] = 1
        more = (i + 1) * P < n_live(b)

        @pl.when(more)
        def _():
            copies(b, i + 1, 1 - slot, "start")

        @pl.when(jnp.logical_not(more))
        def _():
            nxt = jax.lax.fori_loop(
                b + 1, rows, lambda r, f: jnp.where(
                    (f == rows) & (seq_end_ref[r] > 0), r, f), rows)

            @pl.when(nxt < rows)
            def _():
                copies(nxt, 0, 1 - slot, "start")
            dma_ref[1] = (nxt < rows).astype(jnp.int32)

        # column t is token i·P·bs + t, of table entry i·P + t // bs; the
        # columns of a -1 entry are masked like those past seq_end, on one
        # row of columns before it is broadcast over the query rows
        col = jax.lax.broadcasted_iota(jnp.int32, (1, P * bs), 1)
        entry_of = jax.lax.div(col, bs)
        k_pos = i * (P * bs) + col
        keep = k_pos < seq_end_ref[b]
        for k in range(P):
            blk, _ = entry(b, i * P + k)
            keep &= entry_of != jnp.where(blk < 0, k, -1)
        # row r = h * C + c serves chunk column c = r % C of head h
        q_pos = starts_ref[b] + jax.lax.rem(
            jax.lax.broadcasted_iota(jnp.int32, (hc, P * bs), 0), chunk)
        mask = (q_pos >= k_pos) & keep

        copies(b, i, slot, "wait")
        c = buf_ref[slot].reshape(P * bs, buf_ref.shape[-1])  # (P*bs, lanes)
        s = jax.lax.dot_general(
            q_ref[0], c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (H*C, P*bs)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(c.dtype), c[:, :v_lanes], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i == ni - 1)
    def _flush():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_latent_attention_pallas(
    q: jax.Array,                      # (B, C, H, lanes)
    pool: jax.Array,                   # (N_blocks, block_size, lanes)
    block_tables: jax.Array,           # (B, M) int32
    starts: jax.Array,                 # (B,) int32
    n_valid: jax.Array,                # (B,) int32
    *,
    block_size: int,
    v_lanes: int,
    scale: float,
    interpret=False,
) -> jax.Array:
    """Paged MQA over a latent pool (absorbed MLA). Returns (B, C, H,
    v_lanes).

    Every query head scores against one latent row per token (all
    ``lanes``: the compressed kv, the rotary key and any zero padding, which
    the query pads with zeros too) and averages the row's first
    ``v_lanes`` lanes (the compressed kv). A grid step gathers P =
    ``latent_blocks_per_step(M, block_size)`` pool blocks of a row by DMA
    through the block table, double-buffered across live steps, and uses
    them for both products.
    """
    B, C, H, lanes = q.shape
    bs = block_size
    assert pool.shape[1:] == (bs, lanes), (pool.shape, bs, lanes)
    M = block_tables.shape[1]
    P = latent_blocks_per_step(M, bs)
    qh = q.transpose(0, 2, 1, 3).reshape(B, H * C, lanes)
    tables = block_tables.astype(jnp.int32)
    starts = starts.astype(jnp.int32)
    seq_end = starts + n_valid.astype(jnp.int32)

    def q_map(b, i, tables, starts, seq_end):
        return (b, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, pl.cdiv(M, P)),
        in_specs=[pl.BlockSpec((1, H * C, lanes), q_map),
                  pl.BlockSpec(memory_space=pl.ANY)],   # the pool stays in HBM
        out_specs=pl.BlockSpec((1, H * C, v_lanes), q_map),
        scratch_shapes=[
            pltpu.VMEM((H * C, 1), jnp.float32),
            pltpu.VMEM((H * C, 1), jnp.float32),
            pltpu.VMEM((H * C, v_lanes), jnp.float32),
            pltpu.VMEM((2, P, bs, lanes), pool.dtype),
            pltpu.SemaphoreType.DMA((2, P)),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_latent_kernel, bs=bs, chunk=C, per_step=P,
                          v_lanes=v_lanes, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H * C, v_lanes), q.dtype),
        # the prefetch crosses rows, so both axes run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="paged_latent_attention",
    )(tables, starts, seq_end, qh, pool)
    return out.reshape(B, H, C, v_lanes).transpose(0, 2, 1, 3)
