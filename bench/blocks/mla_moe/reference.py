"""Plain float32 reference of the DeepSeek-V2 block the benchmark serves.

Straightforward ``jax.numpy`` at ``default_matmul_precision("highest")``:
no kernels, no cache, no paging, and attention as published, not absorbed:
each head's key is ``[c_kv W_UK | k_rope]`` and its value ``c_kv W_UV``,
with YaRN's rotary frequencies and magnitude and the attention scale
``mscale(mscale_all_dim)^2 / sqrt(qk_nope + qk_rope)`` computed here from
the published formula. The router is a softmax over all the router's
experts, greedy top-k, gates renormalised only with ``norm_topk_prob``,
times ``routed_scaling_factor``; of the picks only the held experts'
(``Geometry.experts_held``) are computed, and the shared experts whole,
as the configuration's deployment states. It imports nothing of the program
and takes nothing the program made: each layer's weights are made again
from the seed (``weights.py`` beside it), one layer at a time.

Departures from the published model, all shared by the served path:

  * the token embedding is scaled by ``sqrt(d_model)``;
  * RMSNorm weights are stored as ``1 + scale``;
  * the rotary pairs are the two halves of the rotated dims (rotate-half);
    DeepSeek's checkpoint interleaves the pairs, which with random weights
    is a permutation of the columns of ``wq`` and ``w_dkv``;
  * the routed experts the deployment puts on other chips are left out.

``quant="fp8"`` is the control, as in ``dense/reference.py``: every matmul
operand rounded through float8 e4m3.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..dense.reference import _a, _rms_norm, _w, embed, head_stats
from . import Geometry
from . import weights as W


def yarn_rope(x, g: Geometry):
    """YaRN-scaled rotate-half RoPE at positions 0..S-1. x: (S, N, D)."""
    S, _, D = x.shape
    factor, orig, fast, slow, ms, ms_all = g.yarn
    half = D // 2
    freq = 1.0 / g.rope_theta ** (np.arange(half) * 2.0 / D)

    def corr_dim(rotations):
        return (D * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(g.rope_theta)))

    low = max(math.floor(corr_dim(fast)), 0)
    high = min(math.ceil(corr_dim(slow)), D - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    inv = (freq / factor * ramp + freq * (1.0 - ramp)).astype(np.float32)
    mag = np.float32(_mscale(factor, ms) / _mscale(factor, ms_all))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos = jnp.cos(ang)[:, None, :] * mag
    sin = jnp.sin(ang)[:, None, :] * mag
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mscale(factor: float, m: float) -> float:
    """YaRN's magnitude ``0.1 m ln(factor) + 1``."""
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(g: Geometry) -> float:
    """``mscale(mscale_all_dim)^2 / sqrt(qk_nope + qk_rope)``."""
    factor, _, _, _, _, ms_all = g.yarn
    return _mscale(factor, ms_all) ** 2 / math.sqrt(g.qk_nope + g.qk_rope)


def attention(g: Geometry, h, lw, quant):
    """Causal MLA of one sequence, not absorbed. h: (S, d)."""
    S = h.shape[0]
    dn, r = g.qk_nope, g.kv_lora_rank
    q = jnp.einsum("sd,dhk->shk", _a(h, quant), lw["attn/wq"])
    q = jnp.concatenate([q[..., :dn], yarn_rope(q[..., dn:], g)], -1)
    ckr = _a(h, quant) @ lw["attn/w_dkv"]
    c = _a(_rms_norm(ckr[:, :r], lw["attn/kv_norm"], g.norm_eps), quant)
    k_rope = yarn_rope(ckr[:, None, r:], g)
    k_nope = jnp.einsum("sr,rhn->shn", c, lw["attn/w_uk"])
    v = jnp.einsum("sr,rhv->shv", c, lw["attn/w_uv"])
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (S, g.heads, g.qk_rope))], -1)
    s = jnp.einsum("qhd,khd->hqk", _a(q, quant), _a(k, quant))
    s = s * np.float32(softmax_scale(g))
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khv->qhv", _a(p, quant), _a(v, quant))
    return jnp.einsum("shv,hvd->sd", _a(o, quant), lw["attn/wo"])


def experts(g: Geometry, h, lw, quant):
    """The routed experts held here and the shared experts. h: (S, d)."""
    probs = jax.nn.softmax(_a(h, quant) @ lw["moe/router"], axis=-1)
    top, ids = jax.lax.top_k(probs, g.top_k)
    if g.norm_topk_prob:
        top = top / top.sum(-1, keepdims=True)
    top = top * np.float32(g.routed_scaling)
    lo, hi = g.experts_held
    held = jnp.arange(lo, hi)
    gate = jnp.einsum("sk,ske->se", top,
                      (ids[:, :, None] == held).astype(jnp.float32))
    hq = _a(h, quant)
    act = (jax.nn.silu(jnp.einsum("sd,edf->esf", hq, lw["moe/w_gate"]))
           * jnp.einsum("sd,edf->esf", hq, lw["moe/w_up"]))
    y = jnp.einsum("esf,efd,se->sd", _a(act, quant), lw["moe/w_down"], gate)
    shared = (jax.nn.silu(hq @ lw["moe/ws_gate"]) * (hq @ lw["moe/ws_up"]))
    return y + _a(shared, quant) @ lw["moe/ws_down"]


def _mlp(h, lw, quant):
    hq = _a(h, quant)
    act = jax.nn.silu(hq @ lw["mlp/w_gate"]) * (hq @ lw["mlp/w_up"])
    return _a(act, quant) @ lw["mlp/w_down"]


def _apply(g: Geometry, x, lw, quant, ffn):
    def one(xs):
        xs = xs + attention(g, _rms_norm(xs, lw["ln1"], g.norm_eps), lw,
                            quant)
        return xs + ffn(_rms_norm(xs, lw["ln2"], g.norm_eps), lw, quant)
    return jax.lax.map(one, x)


def _cast(leaves, quant):
    return {k: _w(v, quant) if v.ndim > 1 else v.astype(jnp.float32)
            for k, v in leaves.items()}


@functools.partial(jax.jit, static_argnums=(0, 5))
def dense_layer(g: Geometry, x: jax.Array, lo, hi, index,
                quant: Optional[str] = None) -> jax.Array:
    """Leading dense layer ``index`` applied to x (B, S, d)."""
    with jax.default_matmul_precision("highest"):
        lw = _cast(W.dense_leaves(g, lo, hi, index), quant)
        return _apply(g, x, lw, quant, _mlp)


@functools.partial(jax.jit, static_argnums=(0, 5))
def layer(g: Geometry, x: jax.Array, lo, hi, index,
          quant: Optional[str] = None) -> jax.Array:
    """Expert layer ``index`` applied to x (B, S, d), one sequence at a
    time."""
    with jax.default_matmul_precision("highest"):
        lw = _cast(W.moe_leaves(g, lo, hi, index), quant)
        return _apply(g, x, lw, quant,
                      lambda h, lw, quant: experts(g, h, lw, quant))


def forward_stats(g: Geometry, seed: int, tokens: np.ndarray,
                  asked: np.ndarray, quant: Optional[str] = None):
    """Run the reference over ``tokens`` (B, S), layer by layer, and
    return ``head_stats`` for the token ids ``asked`` (T, B, S)."""
    lo, hi = W.seed_words(seed)
    x = embed(g, lo, hi, jnp.asarray(tokens))
    for i in range(g.layers):
        apply = dense_layer if i < g.dense_layers else layer
        x = apply(g, x, lo, hi, jnp.uint32(i), quant)
    gap, top = head_stats(g, x, lo, hi, jnp.asarray(asked), quant)
    return np.asarray(gap), np.asarray(top)
