"""Mixture-of-experts FFN: router, capacity math, and the reference (oracle)
execution path.

The *transport* of tokens/weights between devices is the Two-Chains jam layer
(``repro.core.dispatch``): ``moe_ffn`` accepts a ``transport`` callable so the
model definition is independent of how bytes move. The default here is the
single-device oracle (dense masked einsum over all experts) — the pure-jnp
``ref`` against which both shard_map transports and the Pallas moe_jam kernel
are validated.
"""
from __future__ import annotations

import math
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.models.common import ParamBuilder, act_fn


class RouteResult(NamedTuple):
    expert_ids: jax.Array    # (N, k) int32
    gates: jax.Array         # (N, k) f32 (``MoEConfig.norm_topk_prob``)
    aux_loss: jax.Array      # () load-balance aux
    z_loss: jax.Array        # () router z-loss


def init_moe(b: ParamBuilder, d_model: int, m: MoEConfig) -> None:
    b.param("router", (d_model, m.num_experts), ("embed", "expert"))
    e = m.num_held
    b.param("w_gate", (e, d_model, m.expert_ff), ("expert", "embed", "moe_ff"), fan_in=d_model)
    b.param("w_up", (e, d_model, m.expert_ff), ("expert", "embed", "moe_ff"), fan_in=d_model)
    b.param("w_down", (e, m.expert_ff, d_model), ("expert", "moe_ff", "embed"), fan_in=m.expert_ff)
    if m.num_shared > 0:
        ff = (m.shared_ff or m.expert_ff) * m.num_shared
        b.param("ws_gate", (d_model, ff), ("embed", "ff"))
        b.param("ws_up", (d_model, ff), ("embed", "ff"))
        b.param("ws_down", (ff, d_model), ("ff", "embed"))


def route_topk(x: jax.Array, router_w: jax.Array, m: MoEConfig) -> RouteResult:
    """x: (N, d) -> top-k routing with Switch-style aux losses (float32 math)."""
    logits = jnp.einsum("nd,de->ne", x.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, m.top_k)               # (N,k)
    if m.norm_topk_prob:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    if m.routed_scaling_factor != 1.0:
        gates = gates * m.routed_scaling_factor
    # load-balance: E * sum_e (frac tokens to e) * (mean prob of e)
    e = m.num_experts
    one_hot = jax.nn.one_hot(ids[:, 0], e, dtype=jnp.float32)  # primary expert
    f = one_hot.mean(0)
    p = probs.mean(0)
    aux = e * jnp.sum(f * p) * m.router_aux_coef
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2) * m.router_z_coef
    return RouteResult(ids.astype(jnp.int32), gates, aux, z)


def expert_capacity(n_tokens: int, m: MoEConfig, n_shards: int = 1) -> int:
    """Per-expert capacity, padded to an MXU-aligned multiple of 8."""
    c = math.ceil(n_tokens * m.top_k * m.capacity_factor / m.num_experts)
    return max(8, -(-c // 8) * 8)


def expert_ffn(w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
               x: jax.Array, act: str = "silu") -> jax.Array:
    """Batched expert FFN: x (E, C, d) with per-expert weights (E, d, f)."""
    g = jnp.einsum("ecd,edf->ecf", x, w_gate)
    u = jnp.einsum("ecd,edf->ecf", x, w_up)
    h = act_fn(act)(g) * u
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def shared_expert_ffn(params, xf: jax.Array, act: str = "silu") -> jax.Array:
    """The always-on shared experts, one gated FFN over xf (N, d)."""
    g = jnp.einsum("nd,df->nf", xf, params["ws_gate"])
    u = jnp.einsum("nd,df->nf", xf, params["ws_up"])
    return jnp.einsum("nf,fd->nd", act_fn(act)(g) * u, params["ws_down"])


def build_dispatch(ids: jax.Array, gates: jax.Array, n_experts: int,
                   capacity: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Capacity-bucketed dispatch plan.

    Returns (slot (N,k) int32 in [0, E*C] — E*C is the drop slot,
             keep (N,k) bool, position-in-expert rank (N,k)).
    """
    n, k = ids.shape
    flat = ids.reshape(-1)                                    # (N*k,)
    one_hot = jax.nn.one_hot(flat, n_experts, dtype=jnp.int32)
    rank = (jnp.cumsum(one_hot, axis=0) - one_hot) * one_hot  # pos within expert
    rank = rank.sum(-1).reshape(n, k)
    keep = rank < capacity
    slot = jnp.where(keep, ids * capacity + rank, n_experts * capacity)
    return slot.astype(jnp.int32), keep, rank


def moe_ffn_oracle(params, x: jax.Array, m: MoEConfig, act: str = "silu",
                   capacity: Optional[int] = None,
                   token_mask: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """Reference MoE: capacity-bucketed single-device execution.

    x: (B, S, d). Returns (out, aux_losses_sum). This is the oracle for the
    jam transports; it performs the same capacity/drop math so distributed
    results match it exactly.

    ``token_mask`` (B, S) bool marks real tokens: masked-out tokens (paged
    serving's padding columns) route to the drop slot with zero gates, so
    they consume no expert capacity and contribute nothing — without it a
    padding column can steal a capacity slot from a real token and change
    its output.
    """
    b, s, d = x.shape
    xf = x.reshape(-1, d)
    n = xf.shape[0]
    r = route_topk(xf, params["router"], m)
    ids, gates = r.expert_ids, r.gates
    if token_mask is not None:
        tm = token_mask.reshape(-1)
        # out-of-range expert id => all-zero one_hot in build_dispatch =>
        # rank 0 and slot == the drop slot: no capacity consumed
        ids = jnp.where(tm[:, None], ids, jnp.int32(m.num_experts))
        gates = gates * tm[:, None]
    c = capacity or expert_capacity(n, m)
    slot, keep, _ = build_dispatch(ids, gates, m.num_experts, c)
    buf = jnp.zeros((m.num_experts * c + 1, d), x.dtype)
    buf = buf.at[slot.reshape(-1)].set(jnp.repeat(xf, m.top_k, axis=0),
                                       mode="drop")
    buf = buf[:-1].reshape(m.num_experts, c, d)
    out_buf = expert_ffn(params["w_gate"], params["w_up"], params["w_down"],
                         buf, act)
    out_buf = jnp.concatenate([out_buf.reshape(-1, d),
                               jnp.zeros((1, d), x.dtype)], axis=0)
    gathered = out_buf[slot.reshape(-1)].reshape(n, m.top_k, d)
    w = (gates * keep).astype(x.dtype)
    y = jnp.einsum("nkd,nk->nd", gathered, w)
    if m.num_shared > 0:
        y = y + shared_expert_ffn(params, xf, act)
    return y.reshape(b, s, d), r.aux_loss + r.z_loss


def moe_ffn_held(params, x: jax.Array, m: MoEConfig, act: str = "silu",
                 token_mask: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """The expert layer of one expert-parallel share, dropping no token.

    x: (B, S, d). The router scores all ``num_experts``; of each token's
    top-k picks only those of the held experts ``[lo, hi) =
    m.experts_held`` are computed here (a masked einsum over the held
    experts: every expert's weights are read once, and no token waits on
    another for room), plus the shared experts whole. What the other
    shares' experts add is theirs to compute. Tokens outside
    ``token_mask`` (B, S) contribute nothing and are not counted.

    Returns (out (B, S, d), counters (3,) float32): the top-k picks of the
    fed tokens, those that go to held experts, and the most picks one held
    expert took.
    """
    b, s, d = x.shape
    xf = x.reshape(-1, d)
    lo, hi = m.experts_held
    with jax.named_scope("moe.router"):
        r = route_topk(xf, params["router"], m)
        valid = (jnp.ones((xf.shape[0],), bool) if token_mask is None
                 else token_mask.reshape(-1))
        local = r.expert_ids - lo                                # (N, k)
        here = (local >= 0) & (local < hi - lo) & valid[:, None]
        picks = jax.nn.one_hot(jnp.where(here, local, -1), hi - lo,
                               dtype=jnp.float32)                # (N, k, E)
        w = jnp.einsum("nk,nke->en", r.gates, picks)             # (E, N)
        counters = jnp.stack([valid.sum() * m.top_k, here.sum(),
                              picks.sum((0, 1)).max()]).astype(jnp.float32)
    with jax.named_scope("moe.experts"):
        g = jnp.einsum("nd,edf->enf", xf, params["w_gate"])
        u = jnp.einsum("nd,edf->enf", xf, params["w_up"])
        h = act_fn(act)(g) * u * w[:, :, None].astype(x.dtype)
        y = jnp.einsum("enf,efd->nd", h, params["w_down"])
    if m.num_shared > 0:
        with jax.named_scope("moe.shared"):
            y = y + shared_expert_ffn(params, xf, act)
    return y.reshape(b, s, d), counters


MoETransport = Callable[..., Tuple[jax.Array, jax.Array]]


def moe_ffn(params, x: jax.Array, m: MoEConfig, act: str = "silu",
            transport: Optional[MoETransport] = None,
            token_mask: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, jax.Array]:
    """MoE FFN with pluggable jam transport (None => single-device oracle).

    ``token_mask`` (B, S) bool marks real tokens; both paths honor it with
    the same routing rule (masked tokens hit the drop slot with zero gates,
    consuming no expert capacity — see ``core.dispatch._mask_route``), so
    paged MoE serving works on any mesh (docs/fabric.md).
    """
    if transport is None:
        return moe_ffn_oracle(params, x, m, act, token_mask=token_mask)
    if token_mask is None:
        return transport(params, x, m, act)
    return transport(params, x, m, act, token_mask=token_mask)
