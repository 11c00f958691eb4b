"""Paged serving scheduler tests (smoke model, CPU) — Engine(cache="paged").

Invariants (ISSUE 2 satellite): no block leaks across request lifecycles,
FIFO admission under pressure, and preempted requests finishing with tokens
identical to an unloaded run. Output ground truth is the unbatched greedy
forward (the fixed-slot batcher is only exact for its first admission wave —
docs/serving.md).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.configs.base import SHAPES, RunConfig, ShardingConfig
from repro.configs.registry import get_smoke
from repro.models import model as model_lib
from repro.engine import Engine, Request


@pytest.fixture(scope="module")
def mesh11_module():
    return compat.make_mesh((1, 1), ("data", "model"))


@pytest.fixture(scope="module")
def setup(mesh11_module):
    cfg = get_smoke("llama3.2-1b")
    run = RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                    sharding=ShardingConfig(fsdp_params=False, seq_axis=None))
    with mesh11_module:
        params = jax.jit(lambda k: model_lib.init_params(cfg, k)[0])(
            jax.random.PRNGKey(0))
    return cfg, run, mesh11_module, params


def _mk_server(setup, **kw):
    cfg, run, mesh, params = setup
    args = dict(slots=3, max_len=32, num_blocks=16, block_size=4, chunk=4)
    args.update(kw)
    with mesh:
        s = Engine(cfg, run, mesh, cache="paged", **args)
        s.load_params(params)
    return s


def _greedy_reference(cfg, params, prompt, n):
    toks = [int(t) for t in prompt]
    out = []
    for _ in range(n):
        logits, _, _ = model_lib.forward(cfg, params,
                                         jnp.asarray([toks], jnp.int32))
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        toks.append(nxt)
    return out


def _prompts(cfg, n, rng, lo=4, hi=12):
    return [rng.integers(0, cfg.vocab_size,
                         size=(int(rng.integers(lo, hi)),)).astype(np.int32)
            for _ in range(n)]


def test_serves_all_and_matches_unbatched_greedy(setup):
    cfg, run, mesh, params = setup
    server = _mk_server(setup)
    rng = np.random.default_rng(0)
    prompts = _prompts(cfg, 5, rng)
    with mesh:
        for rid, p in enumerate(prompts):
            server.submit(Request(rid, p, max_new_tokens=4))
        done = server.run_until_drained()
    assert len(done) == 5
    by_rid = {r.rid: r.out_tokens for r in done}
    for rid, p in enumerate(prompts):
        assert by_rid[rid] == _greedy_reference(cfg, params, p, 4), rid


def test_no_block_leak_across_lifecycles(setup):
    """Free-block count must be fully restored after every drain, including
    runs that preempt."""
    cfg, run, mesh, params = setup
    server = _mk_server(setup, slots=2, num_blocks=10, max_len=32)
    rng = np.random.default_rng(1)
    for round_ in range(2):
        with mesh:
            for rid, p in enumerate(_prompts(cfg, 4, rng, lo=8, hi=12)):
                server.submit(Request(round_ * 10 + rid, p,
                                      max_new_tokens=10))
            server.run_until_drained()
        m = server.metrics()
        assert m["free_blocks"] == m["num_blocks"], (round_, m)
        assert all(not e.blocks for e in server._finished)


def test_fifo_admission_under_pressure(setup):
    """With 2 slots and 6 requests, later submissions must never be admitted
    before earlier ones, even when the head request is the biggest."""
    cfg, run, mesh, params = setup
    server = _mk_server(setup, slots=2, num_blocks=8, max_len=32)
    rng = np.random.default_rng(2)
    prompts = _prompts(cfg, 6, rng, lo=10, hi=12)   # head is large too
    with mesh:
        for rid, p in enumerate(prompts):
            server.submit(Request(rid, p, max_new_tokens=6))
        done = server.run_until_drained()
    assert len(done) == 6
    assert server.admission_log == sorted(server.admission_log), \
        f"admission jumped the queue: {server.admission_log}"


def test_preempted_requests_match_unloaded_run(setup):
    """Force pool exhaustion mid-decode; the preempted-and-recomputed request
    must emit exactly the tokens an unloaded (solo) run emits."""
    cfg, run, mesh, params = setup
    # 2 requests x (10 prompt + 14 new) tokens = 6 blocks each; pool of 10
    # cannot hold both at full length -> someone gets preempted
    server = _mk_server(setup, slots=2, num_blocks=10, block_size=4,
                        max_len=32, chunk=4)
    rng = np.random.default_rng(3)
    prompts = _prompts(cfg, 2, rng, lo=10, hi=11)
    with mesh:
        for rid, p in enumerate(prompts):
            server.submit(Request(rid, p, max_new_tokens=14))
        done = server.run_until_drained()
    m = server.metrics()
    assert m["preemptions"] >= 1, "test did not exercise preemption"
    assert len(done) == 2
    by_rid = {r.rid: r.out_tokens for r in done}
    for rid, p in enumerate(prompts):
        ref = _greedy_reference(cfg, params, p, 14)
        assert by_rid[rid] == ref, f"preempted request {rid} diverged"


def test_matches_fixed_slot_server_on_exact_wave(setup):
    """Equal-length single-wave workload: the fixed-slot batcher is exact, so
    both backends must produce identical tokens."""
    cfg, run, mesh, params = setup
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, size=(6,)).astype(np.int32)
               for _ in range(3)]
    paged = _mk_server(setup, slots=3, num_blocks=16)
    with mesh:
        for rid, p in enumerate(prompts):
            paged.submit(Request(rid, p, max_new_tokens=5))
        done_p = paged.run_until_drained()

        contig = Engine(cfg, run, mesh, cache="slots", slots=3,
                        max_len=32)
        contig.load_params(params)
        for rid, p in enumerate(prompts):
            contig.submit(Request(rid, p, max_new_tokens=5))
        done_c = contig.run_until_drained()
    assert ({r.rid: r.out_tokens for r in done_p}
            == {r.rid: r.out_tokens for r in done_c})


def test_chunked_prefill_spans_multiple_ticks(setup):
    """A prompt longer than chunk admits immediately but takes ceil(L/chunk)
    ticks to produce its first token — and still matches the reference."""
    cfg, run, mesh, params = setup
    server = _mk_server(setup, slots=1, num_blocks=16, chunk=4)
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab_size, size=(11,)).astype(np.int32)
    with mesh:
        server.submit(Request(0, prompt, max_new_tokens=3))
        ticks_to_first = 0
        req = None
        while not server.completed and server.ticks < 100:
            server.tick()
            ticks_to_first += 1
            if not req and server.completed:
                req = server.completed[0]
            if server.completed:
                break
            if any(e and e.req.out_tokens for e in server.slot_entry):
                break
    # 11 tokens at chunk=4 -> 3 prefill ticks to the first token
    assert ticks_to_first == 3
    with mesh:
        done = server.run_until_drained()
    assert done[0].out_tokens == _greedy_reference(cfg, params, prompt, 3)


def test_moe_arch_served_paged_matches_reference(mesh11_module):
    """attn_moe blocks run through the paged path; with dropless capacity
    the padding-column routing mask makes outputs exactly reproduce the
    unbatched greedy forward. (With binding capacity, drops are
    batch-shape-dependent for ANY batched MoE serving — docs/serving.md.)"""
    cfg = get_smoke("olmoe-1b-7b")
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=4.0))
    run = RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                    sharding=ShardingConfig(fsdp_params=False, seq_axis=None))
    with mesh11_module:
        server = Engine(cfg, run, mesh11_module, cache="paged", slots=3,
                        max_len=32, num_blocks=12, block_size=4, chunk=4)
        server.load_params()
        rng = np.random.default_rng(6)
        prompts = _prompts(cfg, 3, rng, lo=5, hi=10)
        for rid, p in enumerate(prompts):
            server.submit(Request(rid, p, max_new_tokens=4))
        done = server.run_until_drained()
    by_rid = {r.rid: r.out_tokens for r in done}
    for rid, p in enumerate(prompts):
        assert by_rid[rid] == _greedy_reference(cfg, server.params, p, 4), rid


def test_metrics_schema(setup):
    server = _mk_server(setup)
    m = server.metrics()
    for key in ("ticks", "active_slots", "peak_active_slots", "queued",
                "completed", "num_blocks", "block_size", "chunk",
                "free_blocks", "used_blocks", "peak_used_blocks",
                "occupancy", "preemptions", "ttft_s",
                "paged_kernel", "live_token_fraction",
                "live_token_fraction_mean"):
        assert key in m, key
    for key in ("decisions", "transport_telemetry"):
        assert key in m["fabric"], key
    assert m["paged_kernel"] in ("pallas", "ref")


def test_kernel_auto_identity_run(setup):
    """ISSUE 4 acceptance: greedy outputs through kernel="auto" stay bitwise
    identical to the unbatched reference forward, including under the
    chunked-prefill path, and the resolved path is reported in metrics()."""
    from repro.kernels.paged_attention import resolve_kernel

    cfg, run, mesh, params = setup
    server = _mk_server(setup, kernel="auto")
    rng = np.random.default_rng(7)
    prompts = _prompts(cfg, 4, rng, lo=5, hi=12)
    with mesh:
        for rid, p in enumerate(prompts):
            server.submit(Request(rid, p, max_new_tokens=5))
        done = server.run_until_drained()
    assert len(done) == 4
    by_rid = {r.rid: r.out_tokens for r in done}
    for rid, p in enumerate(prompts):
        assert by_rid[rid] == _greedy_reference(cfg, params, p, 5), rid
    m = server.metrics()
    assert m["paged_kernel"] == resolve_kernel("auto")
    assert 0.0 < m["live_token_fraction_mean"] <= 1.0


def test_kernel_pallas_identity_run(setup):
    """The stash-resident kernel end-to-end through the scheduler (runs
    under the Pallas interpreter off-TPU): greedy tokens must match the
    unbatched reference, and preemption must not disturb that."""
    cfg, run, mesh, params = setup
    server = _mk_server(setup, slots=2, num_blocks=10, max_len=32, chunk=4,
                        kernel="pallas")
    rng = np.random.default_rng(8)
    prompts = _prompts(cfg, 2, rng, lo=10, hi=11)
    with mesh:
        for rid, p in enumerate(prompts):
            server.submit(Request(rid, p, max_new_tokens=10))
        done = server.run_until_drained()
    assert server.metrics()["paged_kernel"] == "pallas"
    assert len(done) == 2
    by_rid = {r.rid: r.out_tokens for r in done}
    for rid, p in enumerate(prompts):
        assert by_rid[rid] == _greedy_reference(cfg, params, p, 10), rid


def test_rejects_non_gqa_arch(setup):
    """Hybrid attention+SSM blocks carry recurrent state beside their k/v,
    which the pool cannot hold (MLA latents it can: tests/test_mla_paged.py)."""
    _, run, mesh, _ = setup
    hybrid_cfg = get_smoke("hymba-1.5b")
    run_hybrid = dataclasses.replace(run, model=hybrid_cfg)
    with pytest.raises(ValueError, match="paged serving supports"):
        with mesh:
            Engine(hybrid_cfg, run_hybrid, mesh, cache="paged", slots=2,
                   max_len=32, num_blocks=8, block_size=4)


def test_pool_too_small_for_one_request_rejected(setup):
    cfg, run, mesh, _ = setup
    with pytest.raises(ValueError, match="cannot hold"):
        with mesh:
            Engine(cfg, run, mesh, cache="paged", slots=2, max_len=64,
                   num_blocks=4, block_size=4)


def test_request_exceeding_max_len_rejected_at_submit(setup):
    """A request that could never finish must fail fast, not crash (or
    starve the queue) mid-serve."""
    cfg, _, _, _ = setup
    server = _mk_server(setup, max_len=32)
    prompt = np.zeros((30,), np.int32)
    with pytest.raises(ValueError, match="exceeds max_len"):
        server.submit(Request(0, prompt, max_new_tokens=10))
    assert not server.queue
