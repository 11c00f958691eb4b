"""Random weights from the seed, made on the device, in the program's
layout.

Keys as in ``blocks/dense/weights.py``: the seed, the leaf's path, the
layer (0 for the leading dense layer, 1 onward for the expert layers) and,
for a routed expert's leaf, the expert's number among all the router's
experts. So the experts held here are the same arrays whatever share holds
them, and the reference (``reference.py``) rebuilds one layer alone. The
top leaves (``embed``, ``head``, ``final_norm``) are the dense block's.

Tree: ``groups[0][0]`` the leading dense layers, ``groups[1][0]`` the
expert layers, each group stacked on a leading axis unless it holds one
layer (the program's layer plan); each layer with ``ln1``, ``ln2``,
``attn.{wq (d, H, dn+dr), w_dkv (d, r+dr), kv_norm (r,), w_uk (r, H, dn),
w_uv (r, H, dv), wo (H, dv, d)}``, then ``mlp.{w_gate, w_up (d, F), w_down
(F, d)}`` or ``moe.{router (d, E), w_gate, w_up (E_held, d, f), w_down
(E_held, f, d), ws_gate, ws_up (d, S*f), ws_down (S*f, d)}``. Norm weights
are ``1 + scale`` with small non-zero scales.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..dense.weights import (NORM_STD, _base_key, _draw, _leaf_key, _nest,
                             seed_words, top_leaf, top_shapes)
from . import Geometry

__all__ = ["seed_words", "dense_leaves", "moe_leaves", "make_params",
           "top_leaf"]

Shapes = Dict[str, Tuple[Tuple[int, ...], float]]


def attn_shapes(g: Geometry) -> Shapes:
    d, h, r = g.d_model, g.heads, g.kv_lora_rank
    return {
        "ln1": ((d,), NORM_STD),
        "ln2": ((d,), NORM_STD),
        "attn/wq": ((d, h, g.qk_nope + g.qk_rope), 1 / math.sqrt(d)),
        "attn/w_dkv": ((d, r + g.qk_rope), 1 / math.sqrt(d)),
        "attn/kv_norm": ((r,), NORM_STD),
        "attn/w_uk": ((r, h, g.qk_nope), 1 / math.sqrt(r)),
        "attn/w_uv": ((r, h, g.v_head_dim), 1 / math.sqrt(r)),
        "attn/wo": ((h, g.v_head_dim, d), 1 / math.sqrt(h * g.v_head_dim)),
    }


def dense_shapes(g: Geometry) -> Shapes:
    d, f = g.d_model, g.d_ff
    return {**attn_shapes(g),
            "mlp/w_gate": ((d, f), 1 / math.sqrt(d)),
            "mlp/w_up": ((d, f), 1 / math.sqrt(d)),
            "mlp/w_down": ((f, d), 1 / math.sqrt(f))}


def moe_shapes(g: Geometry) -> Shapes:
    """The expert layer's leaves but the routed experts'."""
    d, sf = g.d_model, g.shared * g.expert_ff
    return {**attn_shapes(g),
            "moe/router": ((d, g.experts), 1 / math.sqrt(d)),
            "moe/ws_gate": ((d, sf), 1 / math.sqrt(d)),
            "moe/ws_up": ((d, sf), 1 / math.sqrt(d)),
            "moe/ws_down": ((sf, d), 1 / math.sqrt(sf))}


def expert_shapes(g: Geometry) -> Shapes:
    """One routed expert's leaves."""
    d, f = g.d_model, g.expert_ff
    return {"moe/w_gate": ((d, f), 1 / math.sqrt(d)),
            "moe/w_up": ((d, f), 1 / math.sqrt(d)),
            "moe/w_down": ((f, d), 1 / math.sqrt(f))}


def _draw_all(g: Geometry, lo, hi, shapes: Shapes, folds, dtype):
    base = _base_key(lo, hi)
    out = {}
    for p, (s, std) in shapes.items():
        key = _leaf_key(base, p)
        for f in folds:
            key = jax.random.fold_in(key, f)
        out[p] = _draw(key, s, std, dtype)
    return out


def dense_leaves(g: Geometry, lo, hi, layer, dtype=jnp.bfloat16
                 ) -> Dict[str, jax.Array]:
    """Leading dense layer ``layer``'s leaves, path -> array."""
    return _draw_all(g, lo, hi, dense_shapes(g), (layer,), dtype)


def moe_leaves(g: Geometry, lo, hi, layer, experts=None,
               dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """Expert layer ``layer``'s leaves, path -> array, its routed experts'
    stacked on a leading axis: ``experts`` (uint32 numbers among all the
    router's), by default the held ones."""
    if experts is None:
        experts = jnp.arange(*g.experts_held, dtype=jnp.uint32)
    routed = jax.vmap(lambda e: _draw_all(g, lo, hi, expert_shapes(g),
                                          (layer, e), dtype))(experts)
    return {**_draw_all(g, lo, hi, moe_shapes(g), (layer,), dtype), **routed}


def make_params(g: Geometry, lo, hi, dtype=jnp.bfloat16) -> Dict:
    """The whole tree in the program's layout (trace it under ``jax.jit``
    with the program's parameter shardings as ``out_shardings``)."""
    top = {p: top_leaf(g, lo, hi, p, dtype) for p in top_shapes(g)}

    def group(leaves, first, last):
        """Layers first..last-1, stacked unless there is one."""
        if last - first == 1:
            return [_nest(leaves(jnp.uint32(first)))]
        return [_nest(jax.vmap(leaves)(
            jnp.arange(first, last, dtype=jnp.uint32)))]

    groups = [group(lambda l: moe_leaves(g, lo, hi, l, dtype=dtype),
                    g.dense_layers, g.layers)]
    if g.dense_layers:
        groups.insert(0, group(lambda l: dense_leaves(g, lo, hi, l, dtype),
                               0, g.dense_layers))
    return {**top, "groups": groups}
