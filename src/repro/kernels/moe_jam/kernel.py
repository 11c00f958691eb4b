"""Fused expert-FFN Pallas kernel — the moe_jam "VMEM stash" compute stage.

The Two-Chains stash path executes the active message's function on the
arriving frame *while it is still in near memory* (paper §VII-B: the NIC
stashes code+data into the LLC). On TPU the analogue is this kernel: the
dispatched token bucket for one expert is tiled into VMEM once and the whole
gate/up/act/down chain runs on it before the tile is written back — one HBM
round trip for the activations instead of four (g, u, h, y materialized by
the unfused XLA path).

Grid: ``(E, C/bc, F/bf)`` — experts and capacity tiles are parallel, the
expert-hidden dimension ``f`` is the innermost *arbitrary* (sequential)
dimension so the down-projection accumulates into a VMEM scratch tile.

BlockSpecs (VMEM working set, all MXU-aligned on the trailing dims):
  x      (1, bc, D)   per (e, c, ·)    — token tile, revisited for every f
  w_gate (1, D, bf)   per (e, ·, f)
  w_up   (1, D, bf)   per (e, ·, f)
  w_down (1, bf, D)   per (e, f, ·)
  out    (1, bc, D)   per (e, c, ·)    — written once, at the last f step
  acc    (bc, D) f32  scratch          — the stash accumulator
"""
from __future__ import annotations

import functools

import jax
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu
import jax.numpy as jnp


def _act(h, act: str):
    if act == "silu":
        return h * jax.nn.sigmoid(h)
    if act == "gelu":
        return jax.nn.gelu(h)
    raise ValueError(act)


def _moe_jam_kernel(x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_ref, *, act: str):
    f = pl.program_id(2)
    nf = pl.num_programs(2)

    @pl.when(f == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]                                   # (bc, D)
    g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
    h = (_act(g, act) * u).astype(x.dtype)         # (bc, bf)
    acc_ref[...] += jnp.dot(h, wd_ref[0], preferred_element_type=jnp.float32)

    @pl.when(f == nf - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def moe_jam_ffn_pallas(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                       w_down: jax.Array, *, act: str = "silu",
                       block_c: int = 128, block_f: int = 512,
                       interpret: bool = False) -> jax.Array:
    """x: (E, C, D); w_gate/w_up: (E, D, F); w_down: (E, F, D) -> (E, C, D)."""
    e, c, d = x.shape
    f = w_gate.shape[-1]
    bc = min(block_c, c)
    while c % bc:
        bc -= 1
    bf = min(block_f, f)
    while f % bf:
        bf -= 1

    grid = (e, c // bc, f // bf)
    return pl.pallas_call(
        functools.partial(_moe_jam_kernel, act=act),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, d), lambda e_, c_, f_: (e_, c_, 0)),
            pl.BlockSpec((1, d, bf), lambda e_, c_, f_: (e_, 0, f_)),
            pl.BlockSpec((1, d, bf), lambda e_, c_, f_: (e_, 0, f_)),
            pl.BlockSpec((1, bf, d), lambda e_, c_, f_: (e_, f_, 0)),
        ],
        out_specs=pl.BlockSpec((1, bc, d), lambda e_, c_, f_: (e_, c_, 0)),
        out_shape=jax.ShapeDtypeStruct((e, c, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w_gate, w_up, w_down)
