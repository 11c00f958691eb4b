"""Engine API tests (ISSUE 5): schedule determinism, scheduler
policies, streaming, and unified metrics.

Determinism ground rules: under ``FIFOPolicy`` two independently
constructed engines serving the same workload must produce the *same
schedule* — admission order, tick counts, preemption counts — and emit
bitwise-identical greedy tokens, including through
preemption-and-recompute, on single- and multi-device meshes ((1,4) and
(2,2) over the conftest's 4 simulated CPU devices). Reordering policies
(priority/SJF) must change admission order without changing any
request's tokens (scheduling decides *when*, never *what*). The legacy
``Server``/``PagedServer`` shims these rules were first written against
are gone (docs/engine.md has the migration table); the determinism
tests are their permanent replacement.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro import compat
from repro.configs.base import SHAPES, RunConfig, ShardingConfig
from repro.configs.registry import get_smoke
from repro.engine import (FIFOPolicy, PriorityPolicy, SJFPolicy, Engine,
                          Request, SchedulerState, resolve_policy)
from repro.models import model as model_lib


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


def _mesh(dp: int, tp: int) -> Mesh:
    devs = jax.devices()
    if len(devs) < dp * tp:
        pytest.skip(f"needs {dp * tp} devices, have {len(devs)}")
    return Mesh(np.array(devs[:dp * tp]).reshape(dp, tp), ("data", "model"))


def _mk_engine(setup, **kw):
    cfg, run, mesh, params = setup
    args = dict(cache="paged", slots=3, max_len=32, num_blocks=16,
                block_size=4, chunk=4)
    args.update(kw)
    with mesh:
        e = Engine(cfg, run, mesh, **args)
        e.load_params(params)
    return e


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


def _schedule_fingerprint(server_like):
    return {
        "outputs": {r.rid: list(r.out_tokens) for r in server_like.completed},
        "admission_log": list(server_like.admission_log),
        "ticks": server_like.ticks,
        "preemptions": server_like.preempt_count,
    }


# ---------------------------------------------------------------------------
# legacy parity (the acceptance criterion), (1,4) and (2,2) meshes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dp,tp", [(1, 4), (2, 2)])
def test_paged_engine_matches_legacy_fifo_with_preemption(dp, tp):
    """Two independent Engine(cache='paged') instances under FIFO agree
    bitwise — same tokens, same admission order, same tick/preemption
    counts — on multi-device meshes, with the preemption path
    exercised. (Formerly the legacy-PagedServer parity criterion; the
    shim is gone, determinism against a twin is the invariant.)"""
    cfg = get_smoke("llama3.2-1b")
    run = RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                    sharding=ShardingConfig(fsdp_params=False, seq_axis=None))
    mesh = _mesh(dp, tp)
    kw = dict(slots=2, max_len=32, num_blocks=10, block_size=4, chunk=4)
    rng = np.random.default_rng(3)
    prompts = _prompts(cfg, 2, rng, lo=10, hi=11)
    with mesh:
        eng = Engine(cfg, run, mesh, cache="paged", scheduler="fifo", **kw)
        eng.load_params()
        for rid, p in enumerate(prompts):
            eng.submit(Request(rid, p, max_new_tokens=14))
        eng.run_until_drained()

        legacy = Engine(cfg, run, mesh, cache="paged", scheduler="fifo",
                        **kw)
        legacy.load_params(eng.params)
        for rid, p in enumerate(prompts):
            legacy.submit(Request(rid, p, max_new_tokens=14))
        legacy.run_until_drained()
    assert eng.preempt_count >= 1, "test did not exercise preemption"
    assert _schedule_fingerprint(eng) == _schedule_fingerprint(legacy)


@pytest.mark.parametrize("dp,tp", [(1, 4), (2, 2)])
def test_slots_engine_matches_legacy_fifo(dp, tp):
    """Two independent Engine(cache='slots') instances under FIFO agree
    bitwise on multi-device meshes (two admission waves over 2
    slots)."""
    cfg = get_smoke("llama3.2-1b")
    run = RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                    sharding=ShardingConfig(fsdp_params=False, seq_axis=None))
    mesh = _mesh(dp, tp)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, size=(6,)).astype(np.int32)
               for _ in range(4)]
    with mesh:
        eng = Engine(cfg, run, mesh, cache="slots", slots=2, max_len=32)
        eng.load_params()
        for rid, p in enumerate(prompts):
            eng.submit(Request(rid, p, max_new_tokens=4))
        done_e = eng.run_until_drained()

        legacy = Engine(cfg, run, mesh, cache="slots", slots=2,
                        max_len=32)
        legacy.load_params(eng.params)
        for rid, p in enumerate(prompts):
            legacy.submit(Request(rid, p, max_new_tokens=4))
        done_l = legacy.run_until_drained()
    assert len(done_e) == len(done_l) == 4
    assert ({r.rid: r.out_tokens for r in done_e}
            == {r.rid: r.out_tokens for r in done_l})
    assert eng.ticks == legacy.ticks


def test_paged_engine_matches_unbatched_greedy(setup):
    """Single-device identity spot check: engine outputs == the unbatched
    greedy forward (the model's definition of the right answer)."""
    cfg, run, mesh, params = setup
    eng = _mk_engine(setup)
    rng = np.random.default_rng(0)
    prompts = _prompts(cfg, 4, rng)
    with mesh:
        for rid, p in enumerate(prompts):
            eng.submit(Request(rid, p, max_new_tokens=4))
        done = eng.run_until_drained()
    assert len(done) == 4
    by_rid = {r.rid: r.out_tokens for r in done}
    for rid, p in enumerate(prompts):
        assert by_rid[rid] == _greedy_reference(cfg, params, p, 4), rid


# ---------------------------------------------------------------------------
# scheduler policies
# ---------------------------------------------------------------------------

def test_priority_policy_reorders_admission(setup):
    """With one slot and everything queued up front, PriorityPolicy must
    admit by priority (desc), ties by submission order — demonstrably NOT
    the FIFO order — while every request's tokens stay greedy-exact."""
    cfg, run, mesh, params = setup
    priorities = [0, 5, 1, 9]
    rng = np.random.default_rng(10)
    prompts = _prompts(cfg, 4, rng, lo=5, hi=8)

    logs = {}
    outputs = {}
    for policy in ("fifo", "priority"):
        eng = _mk_engine(setup, slots=1, scheduler=policy)
        with mesh:
            for rid, p in enumerate(prompts):
                eng.submit(Request(rid, p, max_new_tokens=3,
                                   priority=priorities[rid]))
            eng.run_until_drained()
        logs[policy] = list(eng.admission_log)
        outputs[policy] = {r.rid: list(r.out_tokens) for r in eng.completed}
    assert logs["fifo"] == [0, 1, 2, 3]
    assert logs["priority"] == [3, 1, 2, 0]       # by priority 9, 5, 1, 0
    assert logs["priority"] != logs["fifo"]
    # scheduling decides when, never what
    assert outputs["priority"] == outputs["fifo"]
    # per-request records surface the priorities
    assert [r["priority"] for r in sorted(eng.metrics()["requests"],
                                          key=lambda r: r["rid"])] \
        == priorities


def test_sjf_policy_admits_shortest_prompt_first(setup):
    cfg, run, mesh, params = setup
    rng = np.random.default_rng(11)
    lens = [10, 3, 6]
    prompts = [rng.integers(0, cfg.vocab_size, size=(n,)).astype(np.int32)
               for n in lens]
    eng = _mk_engine(setup, slots=1, scheduler="sjf")
    with mesh:
        for rid, p in enumerate(prompts):
            eng.submit(Request(rid, p, max_new_tokens=2))
        eng.run_until_drained()
    assert eng.admission_log == [1, 2, 0]         # by prompt length 3, 6, 10
    assert len(eng.completed) == 3


def test_priority_policy_on_slots_cache(setup):
    """Policies are backend-agnostic: the fixed-slot cache reorders too."""
    cfg, run, mesh, params = setup
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, cfg.vocab_size, size=(5,)).astype(np.int32)
               for _ in range(3)]
    with mesh:
        eng = Engine(cfg, run, mesh, cache="slots", slots=1, max_len=32,
                     scheduler="priority")
        eng.load_params(params)
        for rid, p in enumerate(prompts):
            eng.submit(Request(rid, p, max_new_tokens=2, priority=rid))
        eng.run_until_drained()
    assert eng.admission_log == [2, 1, 0]


def test_custom_policy_object_and_bad_scheduler_rejected(setup):
    cfg, run, mesh, params = setup
    with pytest.raises(ValueError, match="unknown scheduler"):
        with mesh:
            Engine(cfg, run, mesh, cache="paged", slots=2, max_len=32,
                   num_blocks=8, block_size=4, scheduler="lifo")
    with pytest.raises(TypeError, match="SchedulerPolicy"):
        with mesh:
            Engine(cfg, run, mesh, cache="paged", slots=2, max_len=32,
                   num_blocks=8, block_size=4, scheduler=object())
    # a ready policy object passes straight through
    pol = PriorityPolicy()
    assert resolve_policy(pol) is pol
    eng = _mk_engine(setup, scheduler=FIFOPolicy())
    assert eng.policy.name == "fifo"


def test_policy_budget_protocol():
    """budget() is the block-affordability hook: 0 when there is no pool,
    the exact block need when there is one."""
    req = type("R", (), {"priority": 0})()
    entry = type("E", (), {"seq": lambda self: list(range(9)),
                           "prompt_tokens": [], "arrival_seq": 0,
                           "admit_seq": 0, "req": req})()
    blocks_needed = lambda e: -(-(len(e.seq()) + 1) // 4)
    for pol in (FIFOPolicy(), PriorityPolicy(), SJFPolicy()):
        no_pool = SchedulerState(tick=0, free_slots=1, block_budget=None,
                                 blocks_needed=blocks_needed)
        pool = SchedulerState(tick=0, free_slots=1, block_budget=2,
                              blocks_needed=blocks_needed)
        assert pol.budget(entry, no_pool) == 0
        assert pol.budget(entry, pool) == 3       # ceil(10 / 4)
        # 3 needed > 2 budgeted => nobody admits
        assert pol.admit([entry], pool) is None


# ---------------------------------------------------------------------------
# streaming
# ---------------------------------------------------------------------------

def test_stream_tokens_and_callbacks_no_drain(setup):
    """handle.tokens() drives the engine itself; the stream and the
    on_token callbacks both observe exactly the request's final tokens."""
    cfg, run, mesh, params = setup
    eng = _mk_engine(setup, slots=2)
    rng = np.random.default_rng(20)
    prompts = _prompts(cfg, 2, rng, lo=5, hi=9)
    with mesh:
        h0 = eng.submit(Request(0, prompts[0], max_new_tokens=4))
        h1 = eng.submit(Request(1, prompts[1], max_new_tokens=4))
        cb = []
        h0.on_token(lambda tok, i: cb.append((i, tok)))
        streamed0 = list(h0.tokens())             # no run_until_drained
        streamed1 = list(h1.tokens())             # already buffered by now
    assert h0.done and h1.done
    assert streamed0 == h0.req.out_tokens
    assert streamed0 == _greedy_reference(cfg, params, prompts[0], 4)
    assert streamed1 == _greedy_reference(cfg, params, prompts[1], 4)
    assert cb == list(enumerate(streamed0))


def test_stream_survives_preemption(setup):
    """A preempted-and-recomputed request's stream is still exactly its
    final tokens (kept tokens are not re-emitted)."""
    cfg, run, mesh, params = setup
    eng = _mk_engine(setup, slots=2, num_blocks=10, max_len=32)
    rng = np.random.default_rng(3)
    prompts = _prompts(cfg, 2, rng, lo=10, hi=11)
    with mesh:
        handles = [eng.submit(Request(rid, p, max_new_tokens=14))
                   for rid, p in enumerate(prompts)]
        streams = [list(h.tokens()) for h in handles]
    assert eng.preempt_count >= 1, "test did not exercise preemption"
    for rid, p in enumerate(prompts):
        ref = _greedy_reference(cfg, params, p, 14)
        assert streams[rid] == ref == handles[rid].req.out_tokens


def test_on_token_late_subscriber_catches_up(setup):
    cfg, run, mesh, params = setup
    eng = _mk_engine(setup, slots=1)
    rng = np.random.default_rng(21)
    prompt = _prompts(cfg, 1, rng, lo=5, hi=6)[0]
    with mesh:
        h = eng.submit(Request(0, prompt, max_new_tokens=4))
        eng.run_until_drained()
        late = []
        h.on_token(lambda tok, i: late.append(tok))
        assert h.result() is h.req                  # result() is a no-op now
    assert late == h.req.out_tokens


def test_stream_max_ticks_is_stall_bound_not_lifetime_bound():
    """Regression (ISSUE 7 satellite): tokens(max_ticks=) must bound ticks
    *without progress* and reset whenever a token arrives — the old
    counter bounded request lifetime, so any slow-but-progressing stream
    (here: one token every 3rd tick) died once total ticks passed the
    bound even though it was never stalled."""
    from repro.engine.stream import RequestHandle

    class StubReq:
        rid, done = 0, False
        def __init__(self):
            self.out_tokens = []

    class StubEngine:
        def __init__(self, req, period, total):
            self.req, self.period, self.total, self.ticks = \
                req, period, total, 0
        def pending(self):
            return not self.req.done
        def tick(self):
            self.ticks += 1
            if self.period and self.ticks % self.period == 0:
                self.req.out_tokens.append(len(self.req.out_tokens))
                self.req.done = len(self.req.out_tokens) >= self.total

    # 8 tokens, one every 3rd tick: 24 total ticks, max stall window 2.
    # max_ticks=4 < 24 would have killed this stream under the old rule.
    req = StubReq()
    eng = StubEngine(req, period=3, total=8)
    assert list(RequestHandle(eng, req).tokens(max_ticks=4)) == list(range(8))
    assert eng.ticks == 24
    # a genuine stall (no token ever) must still trip the bound
    stalled = StubReq()
    h = RequestHandle(StubEngine(stalled, period=0, total=1), stalled)
    with pytest.raises(RuntimeError, match="no progress in 5 engine ticks"):
        list(h.tokens(max_ticks=5))


def test_stream_max_ticks_allows_slow_chunked_prefill(setup):
    """End-to-end shape of the same bug: a 12-token prompt over chunk=4
    spends several ticks prefilling before the first token; a small
    max_ticks must survive the whole generation as long as every stall
    window stays under it."""
    cfg, run, mesh, params = setup
    eng = _mk_engine(setup, slots=1)
    rng = np.random.default_rng(23)
    prompt = _prompts(cfg, 1, rng, lo=12, hi=13)[0]
    with mesh:
        h = eng.submit(Request(0, prompt, max_new_tokens=6))
        streamed = list(h.tokens(max_ticks=4))
    assert streamed == _greedy_reference(cfg, params, prompt, 6)


def test_handle_result_drives_to_completion(setup):
    cfg, run, mesh, params = setup
    eng = _mk_engine(setup, slots=1)
    rng = np.random.default_rng(22)
    prompt = _prompts(cfg, 1, rng, lo=5, hi=6)[0]
    with mesh:
        h = eng.submit(Request(7, prompt, max_new_tokens=3))
        req = h.result()
    assert req.done and len(req.out_tokens) == 3
    assert not eng.pending()


# ---------------------------------------------------------------------------
# unified metrics + per-request records
# ---------------------------------------------------------------------------

def test_unified_metrics_schema_both_backends(setup):
    cfg, run, mesh, params = setup
    core_keys = ("engine", "ticks", "active_slots", "peak_active_slots",
                 "queued", "completed", "preemptions", "ttft_s", "requests",
                 "transport_decisions", "transport_telemetry", "fabric")
    paged = _mk_engine(setup)
    with mesh:
        slots = Engine(cfg, run, mesh, cache="slots", slots=2, max_len=32)
        slots.load_params(params)
    for eng, cache, step in ((paged, "paged", "engine.paged_step"),
                             (slots, "slots", "engine.decode")):
        m = eng.metrics()
        for key in core_keys:
            assert key in m, (cache, key)
        assert m["engine"]["cache"] == cache
        assert m["engine"]["scheduler"] == "fifo"
        # fabric-routed placement: the registered steps resolve "local"
        assert m["fabric"]["placements"][step] == "local"
    # paged extras keep the legacy names
    pm = paged.metrics()
    for key in ("num_blocks", "block_size", "chunk", "free_blocks",
                "used_blocks", "peak_used_blocks", "occupancy",
                "paged_kernel", "live_token_fraction",
                "live_token_fraction_mean"):
        assert key in pm, key
    assert m["fabric"]["placements"]["engine.prefill"] == "local"


def test_fabric_records_step_calls(setup):
    """Every tick's step invocation goes through fabric.call — the call
    counter is the proof of the one-seam routing."""
    cfg, run, mesh, params = setup
    eng = _mk_engine(setup, slots=2)
    rng = np.random.default_rng(30)
    with mesh:
        for rid, p in enumerate(_prompts(cfg, 2, rng, lo=4, hi=6)):
            eng.submit(Request(rid, p, max_new_tokens=3))
        eng.run_until_drained()
    m = eng.metrics()
    assert m["fabric"]["calls"]["engine.paged_step"] >= eng.ticks


def test_request_arrival_tick_priority_and_ttft_records(setup):
    cfg, run, mesh, params = setup
    eng = _mk_engine(setup, slots=1)
    rng = np.random.default_rng(31)
    prompts = _prompts(cfg, 2, rng, lo=4, hi=6)
    with mesh:
        eng.submit(Request(0, prompts[0], max_new_tokens=3, priority=2))
        eng.run_until_drained()
        # second request arrives after the engine has ticked
        eng.submit(Request(1, prompts[1], max_new_tokens=3))
        eng.run_until_drained()
    recs = {r["rid"]: r for r in eng.metrics()["requests"]}
    assert recs[0]["arrival_tick"] == 0 and recs[0]["priority"] == 2
    assert recs[1]["arrival_tick"] > 0 and recs[1]["priority"] == 0
    for rec in recs.values():
        assert rec["done"] and rec["ttft_s"] is not None
        assert rec["first_token_tick"] >= rec["arrival_tick"]
    # the sorted TTFT distribution matches the per-request records
    assert eng.metrics()["ttft_s"] == sorted(
        r["ttft_s"] for r in recs.values())


# ---------------------------------------------------------------------------
# deprecation contract: the PR-5 shims are GONE, not just deprecated
# ---------------------------------------------------------------------------

def test_server_shims_removed():
    """``repro.runtime.server`` was deleted once every caller had moved
    to ``repro.engine`` — importing it must fail loudly, not resurrect a
    second serving surface."""
    with pytest.raises(ModuleNotFoundError):
        import repro.runtime.server  # noqa: F401


def test_engine_rejects_bad_cache_kind(setup):
    cfg, run, mesh, _ = setup
    with pytest.raises(ValueError, match="cache must be"):
        with mesh:
            Engine(cfg, run, mesh, cache="ring", slots=1, max_len=32)
    with pytest.raises(ValueError, match="requires num_blocks"):
        with mesh:
            Engine(cfg, run, mesh, cache="paged", slots=1, max_len=32)


# ---------------------------------------------------------------------------
# SequenceState backends (ISSUE 6): conformance, recurrent exactness,
# preemption semantics
# ---------------------------------------------------------------------------

def _fake_entry(seq_len=5):
    class E:
        pos = 0
        blocks = []
        snapshot = None
        def __init__(self):
            self.blocks = []
        def seq(self):
            return list(range(seq_len))
    return E()


def test_sequence_state_conformance_lifecycle():
    """Every backend satisfies the SequenceState protocol and runs the
    same admit/append/grow/evict/serialize lifecycle; only the *cost
    semantics* differ (consumable blocks vs slot rows vs snapshots)."""
    from repro.engine import (PagedKVState, RecurrentState, SequenceCapacity,
                              SequenceState, SlotKVState)

    template_fn = lambda: {"state": jnp.full((1, 4), 2.0, jnp.float32),
                           "length": jnp.zeros((), jnp.int32)}
    slots_cache = {"state": jnp.zeros((3, 4), jnp.float32),
                   "length": jnp.zeros((), jnp.int32)}
    paged_cache = {"pool": np.arange(8 * 4 * 2, dtype=np.float32)
                   .reshape(8, 4, 2)}

    backends = {
        "paged": (PagedKVState(num_blocks=8, block_size=4), paged_cache),
        "slots": (SlotKVState(slots=3, template_fn=template_fn), slots_cache),
        "recurrent": (RecurrentState(slots=3, template_fn=template_fn),
                      slots_cache),
    }
    for kind, (st8, cache) in backends.items():
        assert isinstance(st8, SequenceState), kind
        assert st8.kind == kind
        e = _fake_entry()
        # admission: validate -> init -> grow -> append
        if kind == "paged":
            assert st8.validate(5, 100, 32) is not None  # over max_len
        else:
            # slots validates length; recurrent state is O(1) — no limit
            expect = None if kind == "recurrent" else "exceeds"
            msg = st8.validate(5, 100, 32)
            assert (msg is None) == (expect is None), kind
        cache = st8.init(e, cache, slot=0)
        assert st8.grow(e, upto_tokens=5) is True
        e.pos = 5
        st8.append(e, 5)
        cap = st8.capacity()
        assert isinstance(cap, SequenceCapacity)
        assert cap.kind == kind and cap.total_units > 0
        buf = st8.serialize(e, cache, slot=0)
        assert isinstance(buf, bytes) and buf[:4] == b"RST1"
        # eviction semantics diverge per backend:
        if kind == "paged":
            held = list(e.blocks)
            assert len(held) == 2                      # ceil((5+?)/4) grown
            st8.evict(e, cache, slot=0)
            assert e.blocks == [] and e.pos == 0       # recompute path
            assert st8.capacity().free_units == 8
        elif kind == "slots":
            with pytest.raises(RuntimeError, match="cannot preempt"):
                st8.evict(e, cache, slot=0)
            assert cap.free_units is None              # not consumable
        else:
            cache2 = st8.evict(e, cache, slot=0)
            assert e.pos == 5                          # resume, not recompute
            assert e.snapshot is not None
            assert st8.snapshots_taken == 1
            # re-admission restores the snapshot into a different slot
            # (init had templated slot 0 to 2.0 — that is what was
            # snapshotted and must land in slot 2)
            cache2 = st8.init(e, cache2, slot=2)
            assert e.snapshot is None
            assert st8.snapshots_restored == 1
            np.testing.assert_array_equal(
                np.asarray(cache2["state"][2]), np.full(4, 2.0))
        st8.release(e)


def test_paged_gather_ambiguous_block_axis_raises():
    """Regression (ISSUE 7 satellite): ``PagedKVState.gather`` locates the
    pool's (num_blocks, block_size) axis pair structurally; a leaf where
    two adjacent dim pairs both match (e.g. a head dim colliding with the
    pool geometry) must raise instead of silently gathering the first
    match and serializing garbage."""
    from repro.engine import PagedKVState

    st8 = PagedKVState(num_blocks=4, block_size=4)
    e = _fake_entry()
    e.blocks, e.pos = [0, 2], 6
    # (4, 4, 4, 2): dims (0,1) and (1,2) both look like the block pair
    with pytest.raises(ValueError, match="ambiguous block axis"):
        st8.gather(e, {"pool": np.zeros((4, 4, 4, 2), np.float32)}, slot=0)
    # unique pair (dims 1,2 of a scanned-stack leaf) still resolves
    leaf = np.arange(3 * 4 * 4 * 2, dtype=np.float32).reshape(3, 4, 4, 2)
    out = st8.gather(e, {"pool": leaf}, slot=0)
    assert out["pool"].shape == (3, 6, 2)
    np.testing.assert_array_equal(
        out["pool"], leaf[:, [0, 2]].reshape(3, 8, 2)[:, :6])


def test_recurrent_state_template_clears_stale_slot():
    """A freed slot's rows must be re-templated before a fresh request
    runs: recurrent updates integrate whatever state is resident."""
    from repro.engine import RecurrentState
    template_fn = lambda: {"state": jnp.full((1, 4), 2.0, jnp.float32)}
    st8 = RecurrentState(slots=2, template_fn=template_fn)
    cache = {"state": jnp.full((2, 4), 9.0, jnp.float32)}   # stale occupant
    cache = st8.init(_fake_entry(), cache, slot=1)
    np.testing.assert_array_equal(np.asarray(cache["state"]),
                                  [[9.0] * 4, [2.0] * 4])


@pytest.mark.parametrize("arch", ["mamba-130m", "xlstm-1.3b"])
def test_recurrent_engine_greedy_identity_with_requeue(arch):
    """Acceptance: recurrent serving emits greedy tokens bitwise-identical
    to the unbatched plain-cache reference — through chunked prefill
    (prompt lengths on and off the chunk boundary), queue-forced requeue
    (3 requests on 2 slots), and an explicit mid-decode preemption."""
    cfg = get_smoke(arch)
    run = RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                    sharding=ShardingConfig(fsdp_params=False, seq_axis=None))
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    rng = np.random.default_rng(3)
    lens = [4, 5, 7]                      # 4 == chunk: full-chunk prefill
    prompts = [rng.integers(0, cfg.vocab_size, size=(n,)).astype(np.int32)
               for n in lens]
    with mesh:
        eng = Engine(cfg, run, mesh, cache="recurrent", slots=2, max_len=48,
                     chunk=4)
        eng.load_params()
        for rid, p in enumerate(prompts):
            eng.submit(Request(rid, p, max_new_tokens=6))
        # two ticks in, force-evict slot 0's request: its snapshot must
        # resume (never recompute) with identical tokens
        eng.tick(); eng.tick()
        victim = next(e.req.rid for e in eng.slot_entry if e is not None)
        eng.preempt(victim)
        eng.run_until_drained()
        assert eng.preempt_count >= 1
        assert eng.state.snapshots_taken >= 1
        assert eng.state.snapshots_restored >= 1

        def ref_greedy(prompt, n):
            cache = model_lib.init_cache(cfg, 1, 48)
            logits, cache, _ = model_lib.forward(
                cfg, eng.params, jnp.asarray([list(prompt)], jnp.int32),
                cache=cache)
            out = [int(jnp.argmax(logits[0, -1]))]
            for _ in range(n - 1):
                logits, cache, _ = model_lib.forward(
                    cfg, eng.params, jnp.asarray([[out[-1]]], jnp.int32),
                    cache=cache)
                out.append(int(jnp.argmax(logits[0, -1])))
            return out

        done = {r.rid: r.out_tokens for r in eng.completed}
        for rid, p in enumerate(prompts):
            assert done[rid] == ref_greedy(p, 6), f"rid {rid} len {len(p)}"


def test_slots_cache_warns_when_policy_overrides_pick_victim(setup):
    """Regression (ISSUE 6 satellite 1): cache='slots' has no preemption
    path, so a policy that customizes pick_victim must warn instead of
    being silently ignored — and the default FIFO must stay silent."""
    import warnings as _w
    cfg, run, mesh, params = setup
    with mesh:
        with pytest.warns(UserWarning, match="pick_victim will never be "
                                             "consulted"):
            Engine(cfg, run, mesh, cache="slots", slots=1, max_len=32,
                   scheduler="priority")
        with _w.catch_warnings():
            _w.simplefilter("error", UserWarning)
            Engine(cfg, run, mesh, cache="slots", slots=1, max_len=32,
                   scheduler="fifo")


def test_recurrent_cache_rejects_attention_arch(setup):
    cfg, run, mesh, _ = setup             # llama: attention stack
    with pytest.raises(ValueError, match="recurrent serving supports"):
        with mesh:
            Engine(cfg, run, mesh, cache="recurrent", slots=1, max_len=32)


def test_kernel_flag_requires_paged_cache(setup):
    cfg, run, mesh, _ = setup
    for cache in ("slots", "recurrent"):
        with pytest.raises(ValueError, match="paged-attention path"):
            with mesh:
                Engine(cfg, run, mesh, cache=cache, slots=1, max_len=32,
                       kernel="pallas")


def test_default_cache_backend_per_family():
    from repro.configs.registry import default_cache_backend, get_smoke as gs
    expect = {
        "llama3.2-1b": "paged",          # plain GQA -> block pool
        "gemma3-4b": "paged",
        "mamba-130m": "recurrent",       # pure SSM -> constant-size state
        "xlstm-1.3b": "recurrent",
        "hymba-1.5b": "slots",           # hybrid attn+SSM: pool can't hold
        "deepseek-v2-lite-16b": "slots", # MLA latents
        "qwen2-vl-72b": "slots",         # mrope position streams
    }
    for arch, want in expect.items():
        assert default_cache_backend(gs(arch)) == want, arch


def test_engine_cache_auto_resolves_per_family():
    cfg = get_smoke("mamba-130m")
    run = RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                    sharding=ShardingConfig(fsdp_params=False, seq_axis=None))
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    with mesh:
        eng = Engine(cfg, run, mesh, cache="auto", slots=1, max_len=32)
    assert eng.cache_kind == "recurrent"
    assert eng.state.kind == "recurrent"


# ---------------------------------------------------------------------------
# ISSUE 8: stable engine identity, real placements, export/import handoff
# ---------------------------------------------------------------------------

def test_engine_id_in_metrics_identity_block(setup):
    """Satellite: metrics()["engine"] carries a stable engine_id — the
    merge key cluster.metrics() disambiguates replicas by."""
    eng = _mk_engine(setup, engine_id="replica-7")
    m = eng.metrics()["engine"]
    assert m["engine_id"] == "replica-7"
    assert m["placement"] == "local"
    a, b = _mk_engine(setup), _mk_engine(setup)
    assert a.engine_id != b.engine_id            # generated ids stay distinct
    assert a.metrics()["engine"]["engine_id"] == a.engine_id


def test_engine_rejects_bad_placement(setup):
    with pytest.raises(ValueError, match="placement"):
        _mk_engine(setup, placement="teleport")


def test_placement_modes_identical_tokens_and_lease_telemetry(setup):
    """placement= decides where the weights are accounted as living,
    never the math: local/injected/auto emit identical tokens. 'injected'
    acquires the params lease every tick — the first acquire is the
    injection (one miss), later ticks hit warm. Cold 'auto' resolves
    local (injecting a weight tree for one tick's payload never pays) and
    records a cost-model decision per tick."""
    cfg, run, mesh, params = setup
    rng = np.random.default_rng(70)
    prompts = _prompts(cfg, 2, rng, lo=5, hi=9)
    outs, engines = {}, {}
    for placement in ("local", "injected", "auto"):
        eng = _mk_engine(setup, placement=placement)
        with mesh:
            for rid, p in enumerate(prompts):
                eng.submit(Request(rid, p, max_new_tokens=4))
            eng.run_until_drained()
        outs[placement] = {r.rid: list(r.out_tokens) for r in eng.completed}
        engines[placement] = eng
    assert outs["local"] == outs["injected"] == outs["auto"]

    m = engines["local"].metrics()
    assert m["fabric"]["placements"]["engine.paged_step"] == "local"
    assert "engine.paged_step.params" not in m["fabric"]["leases"]

    m = engines["injected"].metrics()
    assert m["fabric"]["placements"]["engine.paged_step"] == "injected"
    lease = m["fabric"]["leases"]["engine.paged_step.params"]
    assert lease["misses"] == 1                  # the injection itself
    assert lease["hits"] == engines["injected"].ticks - 1

    m = engines["auto"].metrics()
    assert m["fabric"]["placements"]["engine.paged_step"] == "local"
    decs = m["transport_decisions"]
    assert len(decs) == engines["auto"].ticks
    assert all(d.endswith("-> local") for d in decs)


def test_inject_params_makes_auto_resolve_injected(setup):
    """inject_params pre-warms the rFaaS lease, so placement='auto'
    serves injected from the first tick — warm reuse ships nothing."""
    cfg, run, mesh, params = setup
    eng = _mk_engine(setup, placement="auto")
    with mesh:
        eng.inject_params(params)
        rng = np.random.default_rng(71)
        p = _prompts(cfg, 1, rng, lo=5, hi=6)[0]
        eng.submit(Request(0, p, max_new_tokens=3))
        eng.run_until_drained()
    m = eng.metrics()
    assert m["fabric"]["placements"]["engine.paged_step"] == "injected"
    lease = m["fabric"]["leases"]["engine.paged_step.params"]
    assert lease["misses"] == 1 and lease["hits"] == eng.ticks
    assert all(d.endswith("-> injected") for d in m["transport_decisions"])


def test_inject_params_places_tree_on_engine_device(setup):
    """A weight tree built on device 0 and injected into an engine whose
    mesh is device 1 lands on device 1 — params and KV pool alike — so
    router replicas sharing one tree each serve from their own device, and
    both serve the same tokens."""
    cfg, run, _, params = setup
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip(f"needs 2 devices, have {len(devs)}")
    params0 = jax.device_put(params, devs[0])
    engines, outs = [], []
    rng = np.random.default_rng(74)
    prompt = _prompts(cfg, 1, rng, lo=6, hi=7)[0]
    for i in (0, 1):
        mesh = compat.make_mesh((1, 1), ("data", "model"), devices=[devs[i]])
        with mesh:
            eng = Engine(cfg, run, mesh, cache="paged", slots=2, max_len=16,
                         num_blocks=8, block_size=4, chunk=4,
                         placement="auto", engine_id=f"dev{i}")
            eng.inject_params(params0)
            eng.submit(Request(0, prompt, max_new_tokens=3))
            outs.append(list(eng.run_until_drained()[0].out_tokens))
        engines.append(eng)
    for i, eng in enumerate(engines):
        for leaf in jax.tree.leaves(eng.params) + jax.tree.leaves(eng.cache):
            assert leaf.devices() == {devs[i]}, (i, leaf.devices())
    assert outs[0] == outs[1]
    # the lease holds the engine's own (placed) arrays: auto stayed warm
    assert engines[1].metrics()["fabric"]["placements"][
        "engine.paged_step"] == "injected"


def test_export_import_roundtrip_and_source_handle_detach(setup):
    """Engine-level handoff: a mid-flight request exports into a ticket,
    the source forgets it (its stream handle raises instead of hanging),
    and the import resumes bitwise-identically on the peer."""
    cfg, run, mesh, params = setup
    a = _mk_engine(setup, engine_id="exp-a")
    b = _mk_engine(setup, engine_id="exp-b")
    rng = np.random.default_rng(72)
    prompt = _prompts(cfg, 1, rng, lo=9, hi=10)[0]
    want = _greedy_reference(cfg, params, prompt, 6)
    with mesh:
        h = a.submit(Request(5, prompt, max_new_tokens=6))
        a.tick(); a.tick()
        ticket = a.export_request(5)
        assert ticket.cache_kind == "paged" and ticket.pos > 0
        assert ticket.state is not None
        assert not a.pending()
        with pytest.raises(RuntimeError, match="left this engine"):
            h.result(max_ticks=5)
        req = b.import_request(ticket).result()
    assert req.out_tokens == want
    assert a.metrics()["migrations"] == {"in": 0, "out": 1}
    assert b.metrics()["migrations"] == {"in": 1, "out": 0}


def test_export_unknown_or_finished_rid_raises(setup):
    cfg, run, mesh, params = setup
    eng = _mk_engine(setup)
    rng = np.random.default_rng(73)
    with mesh:
        eng.submit(Request(0, _prompts(cfg, 1, rng, lo=4, hi=5)[0],
                           max_new_tokens=2))
        eng.run_until_drained()
    with pytest.raises(KeyError, match="finished requests cannot migrate"):
        eng.export_request(0)
    with pytest.raises(KeyError, match="not queued or running"):
        eng.export_request(42)


def test_import_rejects_foreign_cache_kind(setup):
    cfg, run, mesh, params = setup
    a = _mk_engine(setup)
    rng = np.random.default_rng(74)
    with mesh:
        slots_eng = Engine(cfg, run, mesh, cache="slots", slots=2,
                           max_len=32)
        slots_eng.load_params(params)
        a.submit(Request(0, _prompts(cfg, 1, rng, lo=5, hi=6)[0],
                         max_new_tokens=4))
        a.tick()
        ticket = a.export_request(0)
        with pytest.raises(ValueError,
                           match="do not convert across backends"):
            slots_eng.import_request(ticket)
