"""Chunked selective-scan Pallas kernel (hymba's Mamba path hot-spot).

The recurrence is O(S) sequential, but only in the *chunk* dimension: the
grid walks time chunks sequentially while the (I, N) hidden state lives in
VMEM scratch between chunk steps — HBM sees each input element exactly once
and the state never spills (the scan analogue of the mailbox stash: state
stays in near memory between arrivals).

Grid: ``(B, S/tc)`` — batch parallel, chunks arbitrary (sequential).

BlockSpecs:
  dt, x (1, tc, I) per (b, j)
  b, c  (1, tc, N) per (b, j)
  a     (I, N)     whole (broadcast over grid)
  y     (1, tc, I) per (b, j)
  h_out (1, I, N)  per (b, ·)   — final state, written at the last chunk
  scratch: h (I, N) f32
"""
from __future__ import annotations

import functools

import jax
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu
import jax.numpy as jnp


def _ssm_scan_kernel(dt_ref, b_ref, c_ref, x_ref, a_ref, h0_ref,
                     y_ref, hout_ref, h_ref, *, tc: int):
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)

    a = a_ref[...].astype(jnp.float32)               # (I, N)

    def step(t, h):
        dt_t = dt_ref[0, t, :].astype(jnp.float32)   # (I,)
        x_t = x_ref[0, t, :].astype(jnp.float32)
        b_t = b_ref[0, t, :].astype(jnp.float32)     # (N,)
        c_t = c_ref[0, t, :].astype(jnp.float32)
        da = jnp.exp(dt_t[:, None] * a)              # (I, N)
        h = da * h + (dt_t * x_t)[:, None] * b_t[None, :]
        y_t = jnp.sum(h * c_t[None, :], axis=1)      # (I,)
        y_ref[0, t, :] = y_t.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, tc, step, h_ref[...])
    h_ref[...] = h

    @pl.when(j == nj - 1)
    def _flush():
        hout_ref[0] = h

def ssm_scan_pallas(dt: jax.Array, b: jax.Array, c: jax.Array, x: jax.Array,
                    a: jax.Array, h0: jax.Array | None = None, *,
                    chunk: int = 256, interpret: bool = False):
    """dt/x: (B,S,I); b/c: (B,S,N); a: (I,N); h0: (B,I,N) f32 or None.

    Returns (y (B,S,I) x.dtype, h_last (B,I,N) f32).
    """
    B, S, I = x.shape
    N = b.shape[-1]
    h0 = jnp.zeros((B, I, N), jnp.float32) if h0 is None else h0
    tc = min(chunk, S)
    while S % tc:
        tc -= 1

    grid = (B, S // tc)
    y, h_last = pl.pallas_call(
        functools.partial(_ssm_scan_kernel, tc=tc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tc, I), lambda b_, j_: (b_, j_, 0)),
            pl.BlockSpec((1, tc, N), lambda b_, j_: (b_, j_, 0)),
            pl.BlockSpec((1, tc, N), lambda b_, j_: (b_, j_, 0)),
            pl.BlockSpec((1, tc, I), lambda b_, j_: (b_, j_, 0)),
            pl.BlockSpec((I, N), lambda b_, j_: (0, 0)),
            pl.BlockSpec((1, I, N), lambda b_, j_: (b_, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, tc, I), lambda b_, j_: (b_, j_, 0)),
            pl.BlockSpec((1, I, N), lambda b_, j_: (b_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, I), x.dtype),
            jax.ShapeDtypeStruct((B, I, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((I, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(dt, b, c, x, a, h0)
    return y, h_last
