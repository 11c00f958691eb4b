"""The engine's tracing surface (docs/engine.md#tracing): request
lifecycle stamps, the fed-token counters, the ``engine.*`` profiler spans
inside ``Engine.tick``, and the named scopes of the paged step.

A tiny paged engine on the CPU; each case drives a few ticks by hand so
the expected numbers can be counted on one's fingers.
"""
import re

import jax
import numpy as np
import pytest

from repro import compat
from repro.configs.base import SHAPES, RunConfig, ShardingConfig
from repro.configs.registry import get_smoke
from repro.engine import Engine, Request
from repro.models import model as model_lib

SLOTS, CHUNK = 2, 8
PHASES = ["engine.admit", "engine.schedule", "engine.inputs",
          "engine.dispatch", "engine.fetch", "engine.emit"]


@pytest.fixture(scope="module")
def setup():
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    cfg = get_smoke("llama3.2-1b")
    run = RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                    sharding=ShardingConfig(fsdp_params=False, seq_axis=None))
    with mesh:
        params = jax.jit(lambda k: model_lib.init_params(cfg, k)[0])(
            jax.random.PRNGKey(0))
    return cfg, run, mesh, params


def _engine(setup):
    cfg, run, mesh, params = setup
    with mesh:
        eng = Engine(cfg, run, mesh, cache="paged", slots=SLOTS, max_len=32,
                     num_blocks=16, block_size=4, chunk=CHUNK)
        eng.load_params(params)
    return eng


def _prompt(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, size=(n,)).astype(np.int32)


@pytest.fixture(scope="module")
def served(setup):
    """One 20-token request served to its 4 tokens, per scenario: as is,
    or preempted after its fourth tick (its first decode). Returns the
    engine, the request and its admission stamp before the preemption."""
    cfg, _, mesh, _ = setup
    runs = {}

    def serve(preempt_after):
        if preempt_after not in runs:
            eng = _engine(setup)
            req = Request(0, _prompt(cfg, 20), max_new_tokens=4)
            with mesh:
                eng.submit(req)
                assert req.submitted_at is not None and req.admitted_at is None
                for _ in range(preempt_after or 0):
                    eng.tick()
                first_admission = req.admitted_at
                if preempt_after:
                    eng.preempt(0)
                eng.run_until_drained()
            runs[preempt_after] = eng, req, first_admission
        return runs[preempt_after]
    return serve


@pytest.mark.parametrize("preempt_after", [None, 4])
def test_lifecycle_stamps_are_ordered(served, preempt_after):
    """submitted <= admitted <= first token <= done; a preempted request
    keeps its first admission stamp, and the records carry the wait."""
    engine, req, first_admission = served(preempt_after)
    assert req.done
    assert (req.submitted_at <= req.admitted_at <= req.first_token_at
            <= req.done_at)
    if preempt_after:
        assert engine.preempt_count == 1
        assert req.admitted_at == first_admission
    (rec,) = engine.metrics()["requests"]
    assert rec["queue_wait_s"] == pytest.approx(
        req.admitted_at - req.submitted_at)
    assert rec["ttft_s"] == pytest.approx(
        req.first_token_at - req.submitted_at)


@pytest.mark.parametrize("preempt_after,fed,prefill,decode,steps", [
    # 20 prompt tokens at chunk 8: 8, 8, 4 (first token), then 3 decodes
    (None, 20 + 3, 20, 3, 6),
    # preempted after one decode: 20 + 2 known tokens recompute as 8, 8, 6
    # (third token), then one decode for the fourth
    (4, 20 + 1 + 22 + 1, 20 + 22, 2, 8),
])
def test_fed_token_counters(served, preempt_after, fed, prefill, decode,
                            steps):
    engine, _, _ = served(preempt_after)
    m = engine.metrics()
    assert m["tokens_fed"] == fed
    assert m["prefill_tokens"] == prefill
    assert m["decode_rows"] == decode
    assert m["tokens_fed"] == m["prefill_tokens"] + m["decode_rows"]
    assert m["step_token_slots"] == steps * SLOTS * CHUNK


def test_tick_spans_nest_in_phase_order(setup, tmp_path):
    """Under the profiler, each tick's phase spans lie inside its
    ``engine.tick`` span, in the order the tick runs them."""
    cfg, _, mesh, _ = setup
    engine = _engine(setup)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with mesh:
        engine.submit(Request(0, _prompt(cfg, 12), max_new_tokens=8))
        engine.tick()                   # compile outside the trace
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        for _ in range(3):
            engine.tick()
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    spans = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for plane in data.planes for line in plane.lines
                   for e in line.events if e.name.startswith("engine."))
    ticks = [s for s in spans if s[2] == "engine.tick"]
    assert len(ticks) == 3
    for t0, t1, _ in ticks:
        inner = [name for s0, s1, name in spans
                 if name != "engine.tick" and t0 <= s0 and s1 <= t1]
        assert inner == PHASES


@pytest.fixture(scope="module")
def step_hlo(setup):
    cfg, run, mesh, _ = setup
    with mesh:
        eng = Engine(cfg, run, mesh, cache="paged", slots=SLOTS, max_len=32,
                     num_blocks=16, block_size=4, chunk=CHUNK)
        return eng.lower_step().as_text(dialect="hlo", debug_info=True)


@pytest.mark.parametrize("scope", ["embed", "attention", "kv_pool", "mlp",
                                   "head"])
def test_paged_step_carries_named_scopes(step_hlo, scope):
    op_names = re.findall(r'op_name="([^"]*)"', step_hlo)
    assert any(scope in name.split("/") for name in op_names), scope
