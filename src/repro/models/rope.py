"""Rotary position embeddings: standard, partial (stablelm), YaRN-scaled
(deepseek-v2) and M-RoPE (qwen2-vl)."""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def rope_freqs(head_dim: int, theta: float, rotary_pct: float = 1.0) -> jax.Array:
    """Inverse frequencies for the rotated sub-dimension."""
    rot_dim = int(head_dim * rotary_pct)
    rot_dim -= rot_dim % 2
    if rot_dim == 0:
        return jnp.zeros((0,), jnp.float32)
    exponent = jnp.arange(0, rot_dim, 2, dtype=jnp.float32) / rot_dim
    return 1.0 / (theta ** exponent)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude correction ``0.1 * mscale * ln(factor) + 1``."""
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, yarn) -> jax.Array:
    """YaRN inverse frequencies of a ``dim``-wide rotary head: slots that
    turn more than ``beta_fast`` times over the original context keep their
    frequency, slots that turn fewer than ``beta_slow`` times are divided by
    ``factor``, and a linear ramp joins the two."""
    def corr_dim(rotations: float) -> float:
        return (dim * math.log(yarn.original_max_position_embeddings
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(corr_dim(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = rope_freqs(dim, theta)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return extra / yarn.factor * ramp + extra * (1.0 - ramp)


def _rotate_half(x: jax.Array) -> jax.Array:
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               rotary_pct: float = 1.0, yarn=None) -> jax.Array:
    """Apply RoPE. x: (..., S, H, D); positions: broadcastable to (..., S).
    ``yarn`` (a ``configs.base.YarnConfig``) scales the whole head's
    frequencies and magnitude."""
    head_dim = x.shape[-1]
    scale = 1.0
    if yarn is not None:
        inv = yarn_inv_freq(head_dim, theta, yarn)
        scale = (yarn_mscale(yarn.factor, yarn.mscale)
                 / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
    else:
        inv = rope_freqs(head_dim, theta, rotary_pct)
    rot_dim = 2 * inv.shape[0]
    if rot_dim == 0:
        return x
    ang = positions[..., :, None].astype(jnp.float32) * inv  # (..., S, rot/2)
    ang = jnp.concatenate([ang, ang], axis=-1)               # (..., S, rot)
    cos = jnp.cos(ang)[..., :, None, :] * scale              # (..., S, 1, rot)
    sin = jnp.sin(ang)[..., :, None, :] * scale
    x_rot, x_pass = x[..., :rot_dim], x[..., rot_dim:]
    x_f = x_rot.astype(jnp.float32)
    out = x_f * cos + _rotate_half(x_f) * sin
    return jnp.concatenate([out.astype(x.dtype), x_pass], axis=-1)


def apply_mrope(x: jax.Array, positions_3d: jax.Array, theta: float,
                sections: Tuple[int, int, int]) -> jax.Array:
    """Multimodal RoPE (qwen2-vl): three position streams (t, h, w).

    x: (B, S, H, D). positions_3d: (3, B, S). ``sections`` splits the D/2
    frequency slots among (t, h, w); each slot's angle uses its stream's
    position. For pure-text positions the three streams coincide and this
    reduces to standard RoPE.
    """
    head_dim = x.shape[-1]
    inv = rope_freqs(head_dim, theta, 1.0)                    # (D/2,)
    half = inv.shape[0]
    assert sum(sections) == half, (sections, half)
    # stream index for every frequency slot
    sect_ids = jnp.concatenate([
        jnp.full((s,), i, jnp.int32) for i, s in enumerate(sections)
    ])                                                        # (D/2,)
    pos = positions_3d.astype(jnp.float32)                    # (3, B, S)
    pos_per_slot = pos[sect_ids, :, :]                        # (D/2, B, S)
    ang = jnp.einsum("dbs,d->bsd", pos_per_slot, inv)         # (B, S, D/2)
    ang = jnp.concatenate([ang, ang], axis=-1)                # (B, S, D)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x_f = x.astype(jnp.float32)
    out = x_f * cos + _rotate_half(x_f) * sin
    return out.astype(x.dtype)


def text_mrope_positions(batch: int, seq: int,
                         offset: Optional[jax.Array] = None) -> jax.Array:
    """(3, B, S) position ids where all three streams share text positions."""
    p = jnp.arange(seq, dtype=jnp.int32)[None, :].repeat(batch, axis=0)
    if offset is not None:
        p = p + offset[:, None].astype(jnp.int32)
    return jnp.broadcast_to(p[None], (3, batch, seq))
