"""The expert layer of one expert-parallel share (``moe.moe_ffn_held``).

A layer told which experts it holds routes over all of them, computes only
its own experts' part for the tokens routed there, and drops no token.
Every comparison here runs in float32 on the CPU, where the only difference
between the two sides is the order of a sum: the tolerances are a few
float32 ulps of the outputs' size, far below what dropping or misrouting a
single pick (a whole expert's output times its gate) would move.
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoEConfig
from repro.models import moe as moe_mod
from repro.models.common import ParamBuilder

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "bench"))
from blocks.mla_moe import reference as mla_ref  # noqa: E402

D, F, E, K = 32, 16, 64, 6
SHARES = [(0, 16), (16, 32), (32, 48), (48, 64)]
TOL = 2e-5   # float32 sums of ~70 terms of size ~1 in another order


def _cfg(held=None, **kw) -> MoEConfig:
    kw.setdefault("norm_topk_prob", False)
    return MoEConfig(num_experts=E, top_k=K, expert_ff=F, num_shared=2,
                     shared_ff=F, experts_held=held, **kw)


def _params(seed=0):
    b = ParamBuilder(jax.random.PRNGKey(seed), dtype=jnp.float32)
    moe_mod.init_moe(b, D, _cfg())
    return b.params


def _share(params, lo, hi):
    """The held share's parameters: the router and shared experts whole,
    routed experts lo..hi-1."""
    return {k: v[lo:hi] if k in ("w_gate", "w_up", "w_down") else v
            for k, v in params.items()}


def _x(n, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (1, n, D), jnp.float32)


def _shared_only(params, x):
    act = (jax.nn.silu(x @ params["ws_gate"]) * (x @ params["ws_up"]))
    return act @ params["ws_down"]


def test_shares_sum_to_the_uncut_layer():
    """Four shares of 16 experts, the shared experts counted once, add up
    to the layer that holds all 64, and to the float32 reference's."""
    params = _params()
    x = _x(40)
    uncut, _ = moe_mod.moe_ffn_held(params, x, _cfg((0, E)))
    parts = [moe_mod.moe_ffn_held(_share(params, lo, hi), x, _cfg((lo, hi)))[0]
             for lo, hi in SHARES]
    total = sum(parts) - (len(SHARES) - 1) * _shared_only(params, x)
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=TOL, rtol=0)
    lw = {"moe/" + k: v for k, v in params.items()}
    g = _geometry()
    with jax.default_matmul_precision("highest"):
        want = mla_ref.experts(g, x[0], lw, None)
    np.testing.assert_allclose(np.asarray(uncut[0]), np.asarray(want),
                               atol=TOL, rtol=0)
    # each share's routed part is what the others leave out
    assert all(float(jnp.abs(p - uncut).max()) > 100 * TOL for p in parts)


def _geometry():
    from blocks.mla_moe import Geometry
    return Geometry(
        name="t", layers=2, d_model=D, d_ff=F, vocab=8, heads=1, kv_heads=1,
        head_dim=8, kv_lora_rank=4, qk_nope=4, qk_rope=4, v_head_dim=4,
        dense_layers=1, experts=E, experts_held=(0, E), top_k=K, expert_ff=F,
        shared=2, norm_topk_prob=False, routed_scaling=1.0, rope_theta=1e4,
        yarn=(1.0, 4096, 32.0, 1.0, 1.0, 0.0), norm_eps=1e-6, tied=False)


def test_dropless_token_does_not_depend_on_crowding():
    """Token 0's output is the same alone and beside 63 tokens routed to
    the same experts; the capacity-bucketed layer drops picks there."""
    params = _params()
    m = _cfg((0, E))
    x0 = _x(1)
    crowd = jnp.concatenate([x0] + [x0 * (1 + 1e-3 * i)
                                    for i in range(1, 64)], axis=1)
    alone, _ = moe_mod.moe_ffn_held(params, x0, m)
    crowded, counts = moe_mod.moe_ffn_held(params, crowd, m)
    np.testing.assert_allclose(np.asarray(crowded[0, 0]),
                               np.asarray(alone[0, 0]), atol=TOL, rtol=0)
    assert int(counts[2]) == 64            # one expert took every token
    cap, _ = moe_mod.moe_ffn_oracle(params, crowd, dataclasses.replace(
        m, experts_held=None))
    cap_alone, _ = moe_mod.moe_ffn_oracle(params, x0, dataclasses.replace(
        m, experts_held=None))
    # the capacity layer keeps token 0 (first in its buckets) but drops
    # later tokens' picks: a late token's output loses routed experts
    late_alone, _ = moe_mod.moe_ffn_oracle(params, crowd[:, -1:],
                                           dataclasses.replace(
                                               m, experts_held=None))
    assert float(jnp.abs(cap[0, -1] - late_alone[0, 0]).max()) > 100 * TOL
    np.testing.assert_allclose(np.asarray(cap[0, 0]),
                               np.asarray(cap_alone[0, 0]), atol=TOL, rtol=0)
    late, _ = moe_mod.moe_ffn_held(params, crowd[:, -1:], m)
    np.testing.assert_allclose(np.asarray(crowded[0, -1]),
                               np.asarray(late[0, 0]), atol=TOL, rtol=0)


def test_padding_contributes_nothing_and_is_not_counted():
    params = _params()
    m = _cfg((16, 32))
    x = _x(12)
    mask = jnp.arange(12)[None, :] < 7
    share = _share(params, 16, 32)
    y, counts = moe_mod.moe_ffn_held(share, x, m, token_mask=mask)
    y7, counts7 = moe_mod.moe_ffn_held(share, x[:, :7], m)
    np.testing.assert_allclose(np.asarray(y[:, :7]), np.asarray(y7),
                               atol=TOL, rtol=0)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(counts7))
    assert int(counts[0]) == 7 * K
    r = moe_mod.route_topk(x[0, :7], params["router"], m)
    ids = np.asarray(r.expert_ids)
    assert int(counts[1]) == int(((ids >= 16) & (ids < 32)).sum())
    assert int(counts[2]) == max(int((ids == e).sum()) for e in range(16, 32))


@pytest.mark.parametrize("norm,scale", [(True, 1.0), (False, 1.0),
                                        (False, 2.5)])
def test_route_topk_gates(norm, scale):
    """Gates are the top-k softmax probabilities, renormalised over the
    picks only with ``norm_topk_prob``, times ``routed_scaling_factor``."""
    params = _params()
    x = _x(9)[0]
    m = _cfg(norm_topk_prob=norm, routed_scaling_factor=scale)
    r = moe_mod.route_topk(x, params["router"], m)
    probs = np.asarray(jax.nn.softmax(x @ params["router"], axis=-1))
    top = -np.sort(-probs, axis=-1)[:, :K]
    if norm:
        top = top / top.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(r.gates), top * scale, rtol=1e-5)


def test_default_moe_config_keeps_renormalised_gates():
    assert MoEConfig().norm_topk_prob and MoEConfig().experts_held is None
