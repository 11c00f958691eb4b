"""Transport seam + mesh contract tests.

Covers: ``compat.make_mesh`` (Auto axes) and ``AbstractMesh`` on the
installed JAX, the ``sharded_call`` telemetry, the pure auto-mode decision
(including the per-dp-shard token-count regression), the injected-mode
weight-gather cache, and the grep-level rule that no module outside
``core.transport`` touches raw ``jax.shard_map``.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, AxisType, NamedSharding
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.configs.base import MoEConfig
from repro.core import costmodel
from repro.core import transport as transport_lib
from repro.core.transport import (WeightGatherCache, choose_transport_mode,
                                  sharded_call)

SRC_ROOT = os.path.join(os.path.dirname(__file__), "..", "src")


# ---------------------------------------------------------------------------
# mesh construction + the shard_map seam
# ---------------------------------------------------------------------------

def test_compat_shard_map_runs_on_installed_jax():
    """``sharded_call`` (the repo's one ``jax.shard_map``) builds and runs
    a collective on a ``compat.make_mesh`` mesh."""
    mesh = compat.make_mesh((1,), ("x",))

    def body(v):
        return v + jax.lax.psum(v, "x")

    fn = sharded_call(body, mesh, in_specs=P("x"), out_specs=P("x"),
                      label="test.compat")
    out = fn(jnp.arange(4.0))
    np.testing.assert_allclose(np.asarray(out), np.arange(4.0) * 2)


def test_compat_make_mesh_accepts_and_drops_axis_types():
    """``compat.make_mesh`` takes no ``axis_types``: it always builds Auto
    axes, whatever ``jax.make_mesh`` defaults to. Under an Explicit mesh a
    ``with_sharding_constraint`` and a plain gather inside ``with mesh:``
    raise ``ShardingTypeError`` — the failure that took down the serving
    path."""
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    assert mesh.axis_names == ("data", "model")
    assert dict(mesh.shape) == {"data": 1, "model": 1}
    assert all(t == AxisType.Auto for t in mesh.axis_types)
    sh = NamedSharding(mesh, P("data", None))
    table = jnp.arange(12.0).reshape(6, 2)
    with mesh:
        out = jax.jit(lambda t, i: jax.lax.with_sharding_constraint(
            t[i], sh))(table, jnp.array([[1], [4]]))
    np.testing.assert_allclose(np.asarray(out)[:, 0], [[2.0, 3.0], [8.0, 9.0]])


def test_compat_abstract_mesh_two_arg_form():
    """Device-free meshes (the mesh_util spec tests) default to Auto axes."""
    m = AbstractMesh((16, 16), ("data", "model"))
    assert m.axis_names == ("data", "model")
    assert dict(m.shape) == {"data": 16, "model": 16}
    assert all(t == AxisType.Auto for t in m.axis_types)


def test_no_raw_shard_map_outside_compat():
    """Every shard_map in src/ goes through ``core.transport.sharded_call``
    — the one seam that records transport telemetry."""
    pat = re.compile(r"jax\.shard_map|from jax import shard_map")
    offenders = []
    for dirpath, _, files in os.walk(SRC_ROOT):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            if path.endswith(os.path.join("repro", "core", "transport.py")):
                continue
            with open(path) as fh:
                for lineno, line in enumerate(fh, 1):
                    if pat.search(line):
                        offenders.append(f"{path}:{lineno}: {line.strip()}")
    assert not offenders, "\n".join(offenders)


# ---------------------------------------------------------------------------
# sharded_call telemetry
# ---------------------------------------------------------------------------

def test_sharded_call_records_builds():
    transport_lib.reset_telemetry()
    mesh = compat.make_mesh((1,), ("x",))
    fn = sharded_call(lambda v: v * 2, mesh, in_specs=P("x"),
                      out_specs=P("x"), label="test.double")
    np.testing.assert_allclose(np.asarray(fn(jnp.ones(2))), 2.0)
    tel = transport_lib.get_telemetry()
    assert tel.builds.get("test.double") == 1
    sharded_call(lambda v: v, mesh, in_specs=P("x"), out_specs=P("x"),
                 label="test.double")
    assert transport_lib.get_telemetry().builds["test.double"] == 2


# ---------------------------------------------------------------------------
# auto-mode decision (pure) — per-dp-shard token count regression
# ---------------------------------------------------------------------------

_M = MoEConfig(num_experts=8, top_k=2, expert_ff=512)
_D, _TP = 256, 4


def test_auto_decision_uses_per_dp_shard_tokens():
    """Regression for the cost-model token miscount: with 2 dp shards the
    estimate must see half the global tokens.  At exactly the crossover
    point the buggy global count flips auto-mode to 'injected' one dp-factor
    too early."""
    x = costmodel.crossover_tokens(_M, _D, _TP)   # per-tp-rank flip point
    assert x > 0 and x % 2 == 0
    n_global = x * _TP                             # per-shard on a 1-dp mesh

    # 1 dp shard: the global count IS the shard count -> injected
    chosen1, est1 = choose_transport_mode(
        _M, d_model=_D, batch=1, seq=n_global,
        mesh_shape={"data": 1, "model": _TP}, dp_axes=("data",),
        tp_axis="model", mode="auto")
    assert chosen1 == "injected"
    assert est1.n_tokens_per_tp_rank == x

    # 2 dp shards, same global batch: each shard sees half the tokens ->
    # below the crossover -> local.  (The miscount fed the global count to
    # the estimator and chose injected here.)
    chosen2, est2 = choose_transport_mode(
        _M, d_model=_D, batch=1, seq=n_global,
        mesh_shape={"data": 2, "model": _TP}, dp_axes=("data",),
        tp_axis="model", mode="auto")
    assert est2.n_tokens_per_tp_rank == x // 2
    assert chosen2 == "local"


def test_auto_decision_records_telemetry_and_log():
    transport_lib.reset_telemetry()
    log = []
    chosen, est = choose_transport_mode(
        _M, d_model=_D, batch=2, seq=64,
        mesh_shape={"data": 1, "model": _TP}, dp_axes=("data",),
        tp_axis="model", mode="auto", label="test.jam", log_choice=log)
    assert log == [est]
    assert transport_lib.get_telemetry().decisions == [("test.jam", est)]
    assert est.describe().endswith(est.chosen)


def test_explicit_mode_degrades_to_tp_when_indivisible():
    # 6 tokens per shard cannot split over tp=4
    chosen, est = choose_transport_mode(
        _M, d_model=_D, batch=1, seq=6,
        mesh_shape={"data": 1, "model": _TP}, dp_axes=("data",),
        tp_axis="model", mode="local")
    assert chosen == "tp" and est is None


def test_auto_degrade_telemetry_reports_executed_mode():
    """When the divisibility check overrides auto's preference, the logged
    estimate must say 'tp' — the mode that runs — not the stale preference."""
    transport_lib.reset_telemetry()
    log = []
    chosen, est = choose_transport_mode(
        _M, d_model=_D, batch=1, seq=6,          # 6 % tp != 0 -> degrade
        mesh_shape={"data": 1, "model": _TP}, dp_axes=("data",),
        tp_axis="model", mode="auto", label="test.degrade", log_choice=log)
    assert chosen == "tp"
    assert est.chosen == "tp" and log[0].chosen == "tp"
    assert transport_lib.get_telemetry().decisions[0][1].chosen == "tp"


def test_weight_reuse_amortizes_injected_cost():
    """More reuse -> cheaper injected estimate -> earlier crossover."""
    n = 64 * _TP
    est1 = costmodel.estimate_transport(
        _M, d_model=_D, n_tokens_per_dp_shard=n, tp=_TP, weight_reuse=1)
    est64 = costmodel.estimate_transport(
        _M, d_model=_D, n_tokens_per_dp_shard=n, tp=_TP, weight_reuse=64)
    assert est64.injected_bytes < est1.injected_bytes
    assert est64.local_bytes == est1.local_bytes


# ---------------------------------------------------------------------------
# injected-mode weight-gather cache
# ---------------------------------------------------------------------------

def test_weight_gather_cache_reuses_identical_arrays():
    transport_lib.reset_telemetry()
    cache = WeightGatherCache()
    wg, wu, wd = (jnp.ones((2, 3)), jnp.ones((2, 3)), jnp.ones((3, 2)))
    calls = []

    def gather():
        calls.append(1)
        return ("gathered", len(calls))

    v1 = cache.get_or_gather((wg, wu, wd), gather)
    v2 = cache.get_or_gather((wg, wu, wd), gather)
    assert v1 is v2 and len(calls) == 1

    wd2 = jnp.ones((3, 2))                     # equal value, new identity
    v3 = cache.get_or_gather((wg, wu, wd2), gather)
    assert v3 == ("gathered", 2) and len(calls) == 2

    tel = transport_lib.get_telemetry()
    assert tel.gather_hits == 1 and tel.gather_misses == 2


def test_weight_gather_cache_eviction_bounds_entries():
    cache = WeightGatherCache(capacity=2)
    keys = [(jnp.zeros(i + 1),) for i in range(4)]
    for i, k in enumerate(keys):
        cache.get_or_gather(k, lambda i=i: i)
    assert len(cache._entries) == 2
    # oldest entries evicted; newest still hit
    assert cache.get_or_gather(keys[-1], lambda: "miss") == 3


def test_weight_gather_cache_never_leaks_tracers_to_eager_calls():
    """A jit that closes over concrete weights produces traced gathers from
    concrete keys; caching those would hand a dead trace's tracer to a later
    eager call (UnexpectedTracerError)."""
    cache = WeightGatherCache()
    w = jnp.ones(3)

    @jax.jit
    def f(x):
        full = cache.get_or_gather((w,), lambda: (w * 2 + x,))
        return full[0]

    np.testing.assert_allclose(np.asarray(f(jnp.ones(3))), 3.0)
    # the traced value must NOT have been cached under the concrete key
    out = cache.get_or_gather((w,), lambda: ("fresh",))
    assert out == ("fresh",)
    # and the eager result IS cached and reusable
    assert cache.get_or_gather((w,), lambda: ("again",)) == ("fresh",)


def test_telemetry_summary_is_printable():
    transport_lib.reset_telemetry()
    mesh = compat.make_mesh((1,), ("x",))
    sharded_call(lambda v: v, mesh, in_specs=P("x"), out_specs=P("x"),
                 label="test.summary")
    s = transport_lib.get_telemetry().summary()
    assert "test.summary=1" in s and "gather_cache" in s
