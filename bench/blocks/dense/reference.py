"""Plain float32 reference of the dense decoder the benchmark serves.

Straightforward ``jax.numpy`` at ``default_matmul_precision("highest")``:
no kernels, no cache, no paging. It imports nothing of the program and
takes nothing the program made: each layer's weights are made again from
the seed (``weights.py`` beside it), one layer at a time, so the reference fits
on the chip beside nothing else. It follows the program's dense block,
which departs from the published models in three ways, all shared by the
served path:

  * the token embedding is scaled by ``sqrt(d_model)``;
  * RMSNorm weights are stored as ``1 + scale``;
  * RoPE has no long-context scaling (neither configuration uses any).

``quant="fp8"`` is the control: the same forward with every matmul operand
rounded through float8 (e4m3, scaled per tensor for weights and per row for
activations), the next precision below the bfloat16 that the
configurations state. A check that cannot tell it from the program is too
loose.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import Geometry
from . import weights as W

F8_MAX = 448.0                      # largest finite float8_e4m3fn


def _f8(t: jax.Array, axis: Optional[int]) -> jax.Array:
    """``t`` rounded through float8 e4m3 with a scale per tensor
    (``axis=None``) or per slice along ``axis``."""
    amax = (jnp.max(jnp.abs(t)) if axis is None
            else jnp.max(jnp.abs(t), axis=axis, keepdims=True))
    s = F8_MAX / jnp.maximum(amax, 1e-30)
    return (t * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _w(t: jax.Array, quant: Optional[str]) -> jax.Array:
    t = t.astype(jnp.float32)
    return _f8(t, None) if quant == "fp8" else t


def _a(t: jax.Array, quant: Optional[str]) -> jax.Array:
    return _f8(t, -1) if quant == "fp8" else t


def _rms_norm(x, w, eps):
    return (x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * (1.0 + w.astype(jnp.float32)))


def _rope(x, theta):
    """Rotate-half RoPE over the whole head at positions 0..S-1.
    x: (S, N, D)."""
    S, _, D = x.shape
    half = D // 2
    inv = 1.0 / theta ** (np.arange(half, dtype=np.float32) * 2 / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv       # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(g: Geometry, h, wq, wk, wv, wo, quant):
    """Causal GQA attention of one sequence. h: (S, d)."""
    S = h.shape[0]
    q = _rope(jnp.einsum("sd,dhk->shk", _a(h, quant), wq), g.rope_theta)
    k = _rope(jnp.einsum("sd,dhk->shk", _a(h, quant), wk), g.rope_theta)
    v = jnp.einsum("sd,dhk->shk", _a(h, quant), wv)
    rep = g.heads // g.kv_heads            # query head h reads kv head h // rep
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", _a(q, quant), _a(k, quant))
    s = s / np.float32(np.sqrt(g.head_dim))
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", _a(p, quant), _a(v, quant))
    return jnp.einsum("shk,hkd->sd", _a(o, quant), wo)


@functools.partial(jax.jit, static_argnums=(0,))
def embed(g: Geometry, lo, hi, tokens: jax.Array) -> jax.Array:
    """Hidden states (B, S, d) for tokens (B, S)."""
    with jax.default_matmul_precision("highest"):
        table = W.top_leaf(g, lo, hi, "embed").astype(jnp.float32)
        return table[tokens] * np.float32(np.sqrt(g.d_model))


@functools.partial(jax.jit, static_argnums=(0, 5))
def layer(g: Geometry, x: jax.Array, lo, hi, index, quant: Optional[str] = None
          ) -> jax.Array:
    """Layer ``index`` applied to x (B, S, d), one sequence at a time."""
    with jax.default_matmul_precision("highest"):
        lw = {k: _w(v, quant) if v.ndim > 1 else v.astype(jnp.float32)
              for k, v in W.layer_leaves(g, lo, hi, index).items()}

        def one(xs):
            h = _rms_norm(xs, lw["ln1"], g.norm_eps)
            xs = xs + _attention(g, h, lw["attn/wq"], lw["attn/wk"],
                                 lw["attn/wv"], lw["attn/wo"], quant)
            h = _a(_rms_norm(xs, lw["ln2"], g.norm_eps), quant)
            gate = h @ lw["mlp/w_gate"]
            up = h @ lw["mlp/w_up"]
            return xs + _a(jax.nn.silu(gate) * up, quant) @ lw["mlp/w_down"]

        return jax.lax.map(one, x)


@functools.partial(jax.jit, static_argnums=(0, 5))
def head_stats(g: Geometry, x: jax.Array, lo, hi, tokens: jax.Array,
               quant: Optional[str] = None):
    """Final norm and head, reduced at once so no logits leave the chip.

    tokens: (T, B, S) int32, -1 where not asked. Returns ``gap (T, B, S)``:
    (best logit - logit of the token) / std of that position's logits, and
    ``top (B, S)``: the position's best token."""
    with jax.default_matmul_precision("highest"):
        w_norm = W.top_leaf(g, lo, hi, "final_norm")
        w_head = _w(W.top_leaf(g, lo, hi, "embed").T if g.tied
                    else W.top_leaf(g, lo, hi, "head"), quant)

        def one(args):
            xs, toks = args                                 # (S, d), (T, S)
            logits = _a(_rms_norm(xs, w_norm, g.norm_eps), quant) @ w_head
            best = logits.max(-1)
            std = logits.std(-1)
            picked = jnp.take_along_axis(
                logits[None], jnp.maximum(toks, 0)[..., None], axis=-1)[..., 0]
            gap = jnp.where(toks >= 0, (best - picked) / std, 0.0)
            return gap, jnp.argmax(logits, -1).astype(jnp.int32)

        gap, top = jax.lax.map(one, (x, jnp.swapaxes(tokens, 0, 1)))
        return jnp.swapaxes(gap, 0, 1), top


def forward_stats(g: Geometry, seed: int, tokens: np.ndarray,
                  asked: np.ndarray, quant: Optional[str] = None):
    """Run the reference over ``tokens`` (B, S), layer by layer, and
    return ``head_stats`` for the token ids ``asked`` (T, B, S)."""
    lo, hi = W.seed_words(seed)
    x = embed(g, lo, hi, jnp.asarray(tokens))
    for i in range(g.layers):
        x = layer(g, x, lo, hi, jnp.uint32(i), quant)
    gap, top = head_stats(g, x, lo, hi, jnp.asarray(asked), quant)
    return np.asarray(gap), np.asarray(top)
