"""Random weights from the seed, made on the device.

Every leaf is drawn from its own key: the seed, then the leaf's path, then
(for a leaf stacked over layers) the layer. So one layer's weights can be
made again alone, and the reference (``reference.py``) rebuilds the
very values the program served without taking anything from the program.
The tree has the program's parameter layout: ``embed (V, d)``, ``head
(d, V)`` unless tied, ``final_norm (d,)`` and one group of layers stacked
on a leading axis (``groups[0][0]``), each with ``ln1``, ``ln2``,
``attn.{wq (d, H, D), wk, wv (d, K, D), wo (H, D, d)}`` and
``mlp.{w_gate, w_up (d, F), w_down (F, d)}``. Norm weights are stored as
``1 + scale``; the scales are drawn small and non-zero so that the norms'
weights are exercised.
"""
from __future__ import annotations

import math
import zlib
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from . import Geometry

NORM_STD = 0.1


def seed_words(seed: int) -> Tuple[jax.Array, jax.Array]:
    """A seed of up to 64 bits as two uint32 words (traced arguments, so
    one compiled program serves every seed)."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    return (jnp.uint32(seed & 0xFFFFFFFF), jnp.uint32(seed >> 32))


def _base_key(lo, hi) -> jax.Array:
    return jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), lo),
                              hi)


def _leaf_key(base, path: str) -> jax.Array:
    return jax.random.fold_in(base, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def layer_shapes(g: Geometry) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    """Path -> (shape, std) of one layer's leaves."""
    d, f, h, k, hd = g.d_model, g.d_ff, g.heads, g.kv_heads, g.head_dim
    return {
        "ln1": ((d,), NORM_STD),
        "ln2": ((d,), NORM_STD),
        "attn/wq": ((d, h, hd), 1 / math.sqrt(d)),
        "attn/wk": ((d, k, hd), 1 / math.sqrt(d)),
        "attn/wv": ((d, k, hd), 1 / math.sqrt(d)),
        "attn/wo": ((h, hd, d), 1 / math.sqrt(h * hd)),
        "mlp/w_gate": ((d, f), 1 / math.sqrt(d)),
        "mlp/w_up": ((d, f), 1 / math.sqrt(d)),
        "mlp/w_down": ((f, d), 1 / math.sqrt(f)),
    }


def top_shapes(g: Geometry) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    out = {"embed": ((g.vocab, g.d_model), 1 / math.sqrt(g.d_model)),
           "final_norm": ((g.d_model,), NORM_STD)}
    if not g.tied:
        out["head"] = ((g.d_model, g.vocab), 1 / math.sqrt(g.d_model))
    return out


def _draw(key, shape, std, dtype) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def top_leaf(g: Geometry, lo, hi, path: str, dtype=jnp.bfloat16) -> jax.Array:
    shape, std = top_shapes(g)[path]
    return _draw(_leaf_key(_base_key(lo, hi), path), shape, std, dtype)


def layer_leaves(g: Geometry, lo, hi, layer, dtype=jnp.bfloat16
                 ) -> Dict[str, jax.Array]:
    """Layer ``layer``'s leaves, path -> array."""
    base = _base_key(lo, hi)
    return {p: _draw(jax.random.fold_in(_leaf_key(base, p), layer), s, std,
                     dtype)
            for p, (s, std) in layer_shapes(g).items()}


def _nest(flat: Dict[str, Any]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for path, v in flat.items():
        node = out
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return out


def make_params(g: Geometry, lo, hi, dtype=jnp.bfloat16) -> Dict[str, Any]:
    """The whole tree in the program's layout (trace it under ``jax.jit``
    with the program's parameter shardings as ``out_shardings``)."""
    top = {p: top_leaf(g, lo, hi, p, dtype) for p in top_shapes(g)}
    if g.layers > 1:
        per = jax.vmap(lambda l: layer_leaves(g, lo, hi, l, dtype))(
            jnp.arange(g.layers, dtype=jnp.uint32))
    else:
        per = layer_leaves(g, lo, hi, jnp.uint32(0), dtype)
    return {**top, "groups": [[_nest(per)]]}
