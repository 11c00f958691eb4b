"""Plain float32 reference forward for dense GQA decoder stacks.

Straightforward ``jax.numpy`` in float32 at ``default_matmul_precision
("highest")``: no kernels, no cache, no paging, no batching tricks. It is
written apart from ``models/`` so that a comparison against it also checks
the shared pieces (norms, RoPE, the tied head). It follows the repo's dense
decoder block, which departs from the published Llama 3.2 in three ways,
all shared with the served path:

  * the token embedding is scaled by ``sqrt(d_model)``;
  * RMSNorm weights are stored as ``1 + scale`` (zero-initialised scale);
  * RoPE has no long-context frequency scaling (``rope_scaling`` is not
    modelled).

Supports configs whose every layer is ``attn_full`` with GQA attention,
full rotary dims and a gated MLP (llama3.2-1b, and its smoke config).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import model as model_lib

PyTree = Any


def supports(cfg: ModelConfig) -> bool:
    a = cfg.attention
    return (cfg.family == "dense" and a is not None and a.kind == "gqa"
            and not a.mrope and a.rotary_pct == 1.0 and cfg.mlp_gated
            and cfg.act == "silu" and a.sliding_window is None
            and cfg.frontend.kind == "none"
            and set(model_lib.flat_block_types(cfg)) == {"attn_full"})


def _rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def _rope(x, positions, theta):
    """Rotate-half RoPE over the full head dim. x: (B, S, N, D)."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (np.arange(half, dtype=np.float32) * 2 / x.shape[-1])
    ang = positions[:, :, None].astype(jnp.float32) * inv      # (B, S, D/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(cfg: ModelConfig, x: jax.Array, lp: PyTree) -> jax.Array:
    """One pre-norm decoder layer: GQA attention, then the gated MLP."""
    a = cfg.attention
    H, K, D = a.num_heads, a.num_kv_heads, a.head_dim
    f32 = lambda t: jnp.asarray(t, jnp.float32)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    at, mlp = lp["attn"], lp["mlp"]
    h = _rms_norm(x, f32(lp["ln1"]), cfg.norm_eps)
    q = _rope(jnp.einsum("bsd,dhk->bshk", h, f32(at["wq"])), positions,
              a.rope_theta)
    k = _rope(jnp.einsum("bsd,dhk->bshk", h, f32(at["wk"])), positions,
              a.rope_theta)
    v = jnp.einsum("bsd,dhk->bshk", h, f32(at["wv"]))
    # query head h reads kv head h // (H // K)
    k = jnp.repeat(k, H // K, axis=2)
    v = jnp.repeat(v, H // K, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.float32(np.sqrt(D))
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    x = x + jnp.einsum("bshk,hkd->bsd", o, f32(at["wo"]))
    h = _rms_norm(x, f32(lp["ln2"]), cfg.norm_eps)
    gate = jnp.einsum("bsd,df->bsf", h, f32(mlp["w_gate"]))
    up = jnp.einsum("bsd,df->bsf", h, f32(mlp["w_up"]))
    return x + jnp.einsum("bsf,fd->bsd", jax.nn.silu(gate) * up,
                          f32(mlp["w_down"]))


def forward_f32(cfg: ModelConfig, params: PyTree,
                tokens: jax.Array) -> jax.Array:
    """Logits (B, S, V) in float32 for tokens (B, S), positions 0..S-1."""
    if not supports(cfg):
        raise ValueError(f"{cfg.name}: the reference covers dense GQA "
                         "attn_full stacks only")
    f32 = lambda t: jnp.asarray(t, jnp.float32)
    with jax.default_matmul_precision("highest"):
        embed = f32(params["embed"])
        x = embed[tokens] * np.float32(cfg.d_model ** 0.5)
        for g, (_, repeats) in enumerate(model_lib.layer_plan(cfg)):
            lp = params["groups"][g][0]           # one attn_full per pattern
            if repeats > 1:                       # stacked over the repeats
                x, _ = jax.lax.scan(
                    lambda x, lp: (_layer(cfg, x, lp), None), x, lp)
            else:
                x = _layer(cfg, x, lp)
        x = _rms_norm(x, f32(params["final_norm"]), cfg.norm_eps)
        head = embed.T if cfg.tie_embeddings else f32(params["head"])
        logits = x @ head
    if cfg.final_logit_softcap > 0:
        cap = cfg.final_logit_softcap
        logits = jnp.tanh(logits / cap) * cap
    return logits
