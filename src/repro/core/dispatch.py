"""Jam transports for MoE expert dispatch — the paper's Local vs Injected
function invocation, mapped onto expert parallelism (DESIGN.md §3).

  * ``local``    — paper's Local Function: ship *tokens* (payload) to the
                   resident experts via capacity-bucketed ``all_to_all`` over
                   the tensor/expert axis. The active message is
                   (func_id = expert id, USR = token vectors).
  * ``injected`` — paper's Injected Function: ship *expert weights* (the
                   function state) to the tokens via ``all_gather``; tokens
                   never move. Profitable when token bytes >> weight bytes.
  * ``tp``       — degenerate fallback (no token split possible, e.g. 1
                   token): every rank computes its local experts' share over
                   the full token set; combine with ``psum``.
  * ``auto``     — pick local/injected per shape from ``core.costmodel``
                   (the paper's future-work auto-switch, §VIII).

All transports produce results numerically identical to
``models.moe.moe_ffn_oracle`` modulo capacity-drop boundaries (validated in
tests on a multi-device subprocess). Every body is token-mask-aware
(ISSUE 7): an optional (N,) bool mask routes masked-out tokens — paged
serving's padding columns — to the drop slot with zero gates, the same
rule the oracle applies, so padding can never steal expert capacity from a
real token on any transport.

This module now holds the **per-shard bodies** only; the transport factory
lives in ``repro.fabric.moe`` (reached via ``Fabric.moe_transport`` /
``fabric.call("moe.ffn", ...)``). ``make_jam_transport`` below is a
deprecated shim kept for pre-Fabric callers.
"""
from __future__ import annotations

import warnings
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs.base import MoEConfig
from repro.models.moe import (build_dispatch, expert_capacity, expert_ffn,
                              route_topk, shared_expert_ffn)


def _combine(out_rows: jax.Array, slot: jax.Array, keep: jax.Array,
             gates: jax.Array, dtype) -> jax.Array:
    """Gather expert outputs back to token order and mix with gates."""
    n, k = slot.shape
    d = out_rows.shape[-1]
    padded = jnp.concatenate([out_rows, jnp.zeros((1, d), out_rows.dtype)], 0)
    gathered = padded[slot.reshape(-1)].reshape(n, k, d)
    w = (gates * keep).astype(dtype)
    return jnp.einsum("nkd,nk->nd", gathered, w)


def _scatter_buckets(xf, slot, n_slots):
    """Scatter token rows into capacity buckets; row n_slots is the drop bin."""
    n, d = xf.shape
    k = slot.shape[1]
    buf = jnp.zeros((n_slots + 1, d), xf.dtype)
    buf = buf.at[slot.reshape(-1)].set(
        jnp.repeat(xf, k, axis=0), mode="drop")
    return buf[:-1]


# ---------------------------------------------------------------------------
# per-shard bodies (run inside shard_map)
# ---------------------------------------------------------------------------

def _sp_slice(xf: jax.Array, tp_axis: str) -> Tuple[jax.Array, int]:
    """Sequence/token-parallel slice of the (replicated) token block."""
    tp = jax.lax.axis_size(tp_axis)
    rank = jax.lax.axis_index(tp_axis)
    n = xf.shape[0]
    n_loc = n // tp
    return jax.lax.dynamic_slice_in_dim(xf, rank * n_loc, n_loc, 0), n_loc


def _mask_route(ids: jax.Array, gates: jax.Array,
                tm: Optional[jax.Array], num_experts: int
                ) -> Tuple[jax.Array, jax.Array]:
    """Apply a (N,) token mask to routing the way ``moe_ffn_oracle`` does:
    masked-out tokens (paged serving's padding columns) get an out-of-range
    expert id — all-zero one_hot in ``build_dispatch``, so rank 0 and the
    drop slot, consuming **no capacity** — and zero gates, so they also
    contribute nothing on combine. This is the transports' half of the
    PR-2 token-mask contract (the oracle's half lives in ``models.moe``)."""
    if tm is None:
        return ids, gates
    return (jnp.where(tm[:, None], ids, jnp.int32(num_experts)),
            gates * tm[:, None])


def _aux_pmean(aux: jax.Array, tp_axis: str,
               dp_axes: Tuple[str, ...]) -> jax.Array:
    """Mean the per-shard aux losses over the tensor axis, then every data
    axis — the replicated scalar every transport body must return."""
    aux = jax.lax.pmean(aux, tp_axis)
    for ax in dp_axes:
        aux = jax.lax.pmean(aux, ax)
    return aux


def _local_body(router, wg, wu, wd, shared, xf, tm=None, *, m: MoEConfig,
                act: str, tp_axis: str, dp_axes: Tuple[str, ...]):
    """Local Function mode: token all-to-all to resident experts."""
    tp = jax.lax.axis_size(tp_axis)
    e_loc = wg.shape[0]                       # experts resident on this rank
    e = m.num_experts
    xloc, n_loc = _sp_slice(xf, tp_axis)
    tloc = _sp_slice(tm, tp_axis)[0] if tm is not None else None

    r = route_topk(xloc, router, m)
    ids, gates = _mask_route(r.expert_ids, r.gates, tloc, e)
    cap = expert_capacity(n_loc, m)
    slot, keep, _ = build_dispatch(ids, gates, e, cap)
    buf = _scatter_buckets(xloc, slot, e * cap)             # (E*cap, d)

    # ship token buckets to expert owners (the jam put)
    d = xf.shape[-1]
    send = buf.reshape(tp, e_loc, cap, d)
    recv = jax.lax.all_to_all(send, tp_axis, 0, 0, tiled=False)  # (tp, e_loc, cap, d)
    work = jnp.moveaxis(recv, 0, 1).reshape(e_loc, tp * cap, d)

    out = expert_ffn(wg, wu, wd, work, act)                 # (e_loc, tp*cap, d)

    # return results to token owners (the jam response)
    back = jnp.moveaxis(out.reshape(e_loc, tp, cap, d), 1, 0)
    ret = jax.lax.all_to_all(back, tp_axis, 0, 0, tiled=False)
    rows = ret.reshape(e * cap, d)

    y_loc = _combine(rows, slot, keep, gates, xf.dtype)
    if shared is not None:
        y_loc = y_loc + shared_expert_ffn(shared, xloc, act)

    y = jax.lax.all_gather(y_loc, tp_axis, axis=0, tiled=True)  # (N, d)
    return y, _aux_pmean(r.aux_loss + r.z_loss, tp_axis, dp_axes)


def _injected_body(router, wg_full, wu_full, wd_full, shared, xf, tm=None, *,
                   m: MoEConfig, act: str, tp_axis: str,
                   dp_axes: Tuple[str, ...]):
    """Injected Function mode: expert weights arrive pre-gathered (the
    function state was injected ahead of the call — see the weight-gather
    cache in ``make_jam_transport``); tokens stay put."""
    e = m.num_experts
    xloc, n_loc = _sp_slice(xf, tp_axis)
    tloc = _sp_slice(tm, tp_axis)[0] if tm is not None else None

    r = route_topk(xloc, router, m)
    ids, gates = _mask_route(r.expert_ids, r.gates, tloc, e)
    cap = expert_capacity(n_loc, m)
    slot, keep, _ = build_dispatch(ids, gates, e, cap)
    buf = _scatter_buckets(xloc, slot, e * cap).reshape(e, cap, -1)

    out = expert_ffn(wg_full, wu_full, wd_full, buf, act)   # (E, cap, d)
    rows = out.reshape(e * cap, -1)

    y_loc = _combine(rows, slot, keep, gates, xf.dtype)
    if shared is not None:
        y_loc = y_loc + shared_expert_ffn(shared, xloc, act)

    y = jax.lax.all_gather(y_loc, tp_axis, axis=0, tiled=True)
    return y, _aux_pmean(r.aux_loss + r.z_loss, tp_axis, dp_axes)


def _tp_body(router, wg, wu, wd, shared, xf, tm=None, *, m: MoEConfig,
             act: str, tp_axis: str, dp_axes: Tuple[str, ...]):
    """Fallback: full token set everywhere; each rank serves only its
    resident experts; partial results combined with psum."""
    tp = jax.lax.axis_size(tp_axis)
    rank = jax.lax.axis_index(tp_axis)
    e_loc = wg.shape[0]
    e = m.num_experts
    n = xf.shape[0]

    r = route_topk(xf, router, m)
    ids, gates = _mask_route(r.expert_ids, r.gates, tm, e)
    cap = expert_capacity(n, m)
    # global slots, then mask to my expert range (a masked token's id is e,
    # so its owner is out of every rank's range: nobody computes it)
    slot, keep, _ = build_dispatch(ids, gates, e, cap)
    owner = ids // e_loc
    mine = keep & (owner == rank)
    slot_loc = jnp.where(mine, slot - rank * e_loc * cap, e_loc * cap)
    buf = _scatter_buckets(xf, slot_loc, e_loc * cap).reshape(e_loc, cap, -1)
    out = expert_ffn(wg, wu, wd, buf, act)
    rows = out.reshape(e_loc * cap, -1)
    y_part = _combine(rows, slot_loc, mine, gates, xf.dtype)
    y = jax.lax.psum(y_part, tp_axis)
    if shared is not None:
        # shared weights + tokens are replicated over tp, so adding the
        # shared-expert output on every rank keeps y replicated
        y = y + shared_expert_ffn(shared, xf, act)
    return y, _aux_pmean(r.aux_loss + r.z_loss, tp_axis, dp_axes)


_BODIES = {"local": _local_body, "injected": _injected_body, "tp": _tp_body}


# ---------------------------------------------------------------------------
# transport factory (deprecated shim — the implementation lives in
# repro.fabric.moe, reached through a Fabric so every caller shares the
# cost-model routing, lease pool, and telemetry)
# ---------------------------------------------------------------------------

def make_jam_transport(mesh: Mesh, *, dp_axes: Tuple[str, ...] = ("data",),
                       tp_axis: str = "model", mode: str = "local",
                       weight_reuse: int = 1,
                       log_choice: Optional[list] = None):
    """Build a ``transport(params, x, moe_cfg, act)`` for models.moe.moe_ffn.

    .. deprecated::
        Thin shim over ``repro.fabric.Fabric.moe_transport`` — construct a
        ``Fabric`` and call that instead; it is the same lowering with the
        lease pool and metrics surfaced. Kept so pre-Fabric callers and the
        equivalence tests keep importing from here.
    """
    warnings.warn(
        "repro.core.dispatch.make_jam_transport is deprecated; build a "
        "repro.fabric.Fabric bound to the mesh and use "
        "fabric.moe_transport(...) / fabric.call(...)",
        DeprecationWarning, stacklevel=2)
    from repro.fabric import Fabric
    fabric = Fabric(mesh, dp_axes=dp_axes, tp_axis=tp_axis,
                    name="dispatch.shim")
    return fabric.moe_transport(mode=mode, weight_reuse=weight_reuse,
                                log_choice=log_choice)
