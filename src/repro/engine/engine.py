"""``repro.engine.Engine`` — the one serving engine.

The paper's claim applied to serving: function *invocation* is one uniform
low-granularity API while *placement and scheduling* are chosen dynamically
as the application progresses. Before this module the repro hard-coded
both: the pre-engine fixed-slot and paged servers each owned an admission
loop, a tick loop, preemption logic, and a metrics dialect. ``Engine``
collapsed them:

* **one submit/admit/step/complete loop** (``tick``) over a pluggable
  sequence-state backend behind the ``SequenceState`` protocol
  (``engine.state``) — ``cache="paged"`` (block pool, chunked prefill,
  preempt-and-recompute), ``cache="slots"`` (fixed-slot contiguous cache,
  single-request prefill, no preemption), or ``cache="recurrent"``
  (constant-size SSM/xLSTM state, chunked prefill, snapshot-eviction);
  ``cache="auto"`` picks the model family's default
  (``registry.default_cache_backend``);
* **pluggable scheduling** — a ``SchedulerPolicy`` object
  (``engine.scheduler``) decides admission order, victim selection, and
  block budgets; ``FIFOPolicy`` reproduces the legacy servers bitwise,
  ``PriorityPolicy``/``SJFPolicy`` reorder admission without touching the
  math;
* **streaming outputs** — ``submit`` returns a ``RequestHandle``
  (``engine.stream``): ``handle.tokens()`` yields tokens as ticks produce
  them, ``handle.on_token`` registers callbacks, so clients no longer need
  ``run_until_drained``;
* **fabric-routed invocation** — the jitted serve steps are registered on
  the step bundle's PR-3 ``Fabric`` (``engine.prefill`` / ``engine.decode``
  / ``engine.paged_step``) and every tick invokes them through
  ``fabric.call(..., placement="local")``; ``metrics()["fabric"]`` reports
  per-step call counts and the resolved placement of each registered step.

The ``runtime/server.py`` deprecation shims over this class have been
removed. See docs/engine.md for the API, the scheduler protocol,
streaming semantics, and the migration table from the legacy servers.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
import warnings
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.configs.base import ModelConfig, RunConfig
from repro.core import transport as transport_lib
from repro.core.costmodel import TransportEstimate
from repro.engine.scheduler import (SchedulerPolicy, SchedulerState,
                                    _PolicyBase, resolve_policy)
from repro.engine.state import (BlockPool, PagedKVState, RecurrentState,
                                SequenceState, SlotKVState)
from repro.engine.stream import RequestHandle
from repro.faults.errors import EngineFailedError
from repro.models import model as model_lib
from repro.models.kvcache import state_to_bytes
from repro.runtime.steps import (make_paged_serve_step,
                                 make_recurrent_serve_step, make_serve_step,
                                 sharding_ctx)

PyTree = Any

__all__ = ["Request", "BlockPool", "Engine", "MigrationTicket"]


@dataclasses.dataclass
class Request:
    """One generation request.

    ``priority`` is read by priority-aware scheduler policies (higher =
    more urgent; FIFO/SJF ignore it). ``arrival_tick`` is stamped by
    ``Engine.submit`` with the engine's tick counter at submission and is
    surfaced — together with per-request TTFT — in
    ``metrics()["requests"]``.
    """

    rid: int
    prompt: np.ndarray                  # (L,) int32
    max_new_tokens: int = 16
    priority: int = 0
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    arrival_tick: int = -1              # stamped at submit


@dataclasses.dataclass
class MigrationTicket:
    """Position-independent snapshot of one in-flight request — the unit
    of live migration (``Engine.export_request`` -> wire ->
    ``Engine.import_request``).

    ``state`` is the ``SequenceState.serialize`` buffer covering the first
    ``pos`` tokens of prompt ++ out_tokens (``None`` when nothing is
    resident — the target recomputes from scratch); it carries logical
    token order only, no physical block ids or slot indices, so source and
    target may disagree on pool geometry and mesh. Only the model and the
    ``cache_kind`` must match: a paged buffer cannot restore into a
    recurrent backend (``import_request`` rejects the mismatch loudly).
    """

    rid: int
    cache_kind: str
    priority: int
    max_new_tokens: int
    prompt: List[int]
    out_tokens: List[int]
    pos: int = 0                        # tokens the state buffer covers
    state: Optional[bytes] = None


@dataclasses.dataclass
class _Entry:
    """Scheduler state for one request (states: queued -> running ->
    finished, with running -> queued on preemption)."""

    req: Request
    handle: Optional[RequestHandle] = None
    pos: int = 0                        # tokens resident in the cache
    blocks: List[int] = dataclasses.field(default_factory=list)
    admit_seq: int = -1                 # first-admission stamp (victim order)
    arrival_seq: int = -1               # submit-order stamp (policy ties)
    submit_time: float = 0.0
    first_token_time: Optional[float] = None
    first_token_tick: Optional[int] = None
    preemptions: int = 0
    # prompt as python ints, converted once at submit (seq() runs every tick)
    prompt_tokens: List[int] = dataclasses.field(default_factory=list)
    # recurrent backend: host snapshot of the slot's state at eviction
    snapshot: Any = None
    # migrated-in state buffer, absorbed (then cleared) at admission
    inbound: Optional[bytes] = None

    def seq(self) -> List[int]:
        """prompt ++ generated — what must be resident before decoding."""
        return self.prompt_tokens + self.req.out_tokens


class Engine:
    """One serving engine over one mesh: pluggable scheduler, pluggable
    sequence-state backend, streaming outputs, fabric-routed steps.

    ``cache="paged"``: shared per-layer block pool (``num_blocks`` x
    ``block_size`` tokens), chunked prefill (``chunk`` tokens per tick)
    through the same compiled step as decode, block-budget-gated admission,
    preempt-and-requeue (recompute) on pool exhaustion. ``kernel`` selects
    the paged-attention path (docs/serving.md); on multi-device meshes the
    pallas kernel lowers through ``shard_map`` (kv heads over the tensor
    axis, request rows over the data axes, scheduler arrays replicated) —
    device count never forces the ``ref`` fallback, and MoE archs serve on
    any mesh (the step threads the real-token mask through every jam
    transport).

    ``cache="slots"``: one contiguous per-slot cache of ``max_len``,
    single-request prefill on admission, one decode tick per token — the
    legacy fixed-slot batcher, kept for MLA/hybrid archs and as the
    decode-bench baseline (exactness caveats: docs/serving.md).

    ``cache="recurrent"``: one constant-size state row per slot (SSM /
    xLSTM stacks only), chunked prefill through a masked-recurrence step,
    snapshot-eviction (never a recompute). Each row's recurrence is
    bitwise its unbatched result — the exactness the slots backend cannot
    give mixed-length batches.

    ``cache="auto"``: the model family's default backend
    (``registry.default_cache_backend``).

    ``scheduler`` is a policy name (``"fifo"``/``"priority"``/``"sjf"``) or
    any ``SchedulerPolicy`` object. FIFO reproduces the legacy servers
    bitwise, preemption paths included (tests/test_engine.py).
    """

    _ids = itertools.count()            # default engine_id allocator

    def __init__(self, cfg: ModelConfig, run: RunConfig, mesh: Mesh, *,
                 cache: str = "paged", slots: int, max_len: int,
                 scheduler="fifo", kernel: str = "auto",
                 num_blocks: Optional[int] = None, block_size: int = 16,
                 chunk: int = 8, eos_id: Optional[int] = None,
                 engine_id: Optional[str] = None, placement: str = "local"):
        assert not cfg.is_encoder, "encoder-only arch has no decode path"
        if placement not in ("local", "injected", "auto"):
            raise ValueError(f"placement must be 'local', 'injected', or "
                             f"'auto', got {placement!r}")
        if cache == "auto":
            from repro.configs import registry as registry_lib
            cache = registry_lib.default_cache_backend(cfg)
        if cache not in ("paged", "slots", "recurrent"):
            raise ValueError(
                f"cache must be 'paged', 'slots', or 'recurrent', "
                f"got {cache!r}")
        if kernel != "auto" and cache != "paged":
            raise ValueError(
                f"kernel={kernel!r} selects a paged-attention path; it has "
                f"no effect with cache={cache!r} — drop it or use "
                "cache='paged'")
        self.cfg, self.run, self.mesh = cfg, run, mesh
        self.cache_kind = cache
        self.engine_id = engine_id or f"engine-{next(Engine._ids)}"
        self.placement = placement
        self.slots, self.max_len, self.eos_id = slots, max_len, eos_id
        self.policy: SchedulerPolicy = resolve_policy(scheduler)
        self.params: Optional[PyTree] = None
        self.cache: Optional[PyTree] = None
        self.ticks = 0
        self.completed: List[Request] = []
        self.queue: List[_Entry] = []
        self.slot_entry: List[Optional[_Entry]] = [None] * slots
        self._finished: List[_Entry] = []
        self._submit_counter = 0
        self._admit_counter = 0
        self.admission_log: List[int] = []     # rids in first-admission order
        self.peak_active = 0
        self.preempt_count = 0
        self.migrations_in = 0
        self.migrations_out = 0
        self._placements: Dict[str, str] = {}
        self._pending_pump: List[_Entry] = []
        self._params_nbytes_memo: Optional[int] = None
        # chaos/recovery surface (repro.faults): a failed engine refuses
        # tick/submit/export until restart(); fault_hook fires between
        # placement resolution and step execution (the lease-race window);
        # lease_fallbacks counts auto→injected resolutions demoted to
        # local because the params lease expired inside that window
        self.failed_reason: Optional[str] = None
        self.fault_hook: Optional[Callable[[str], None]] = None
        self.lease_fallbacks = 0

        run_decode = dataclasses.replace(
            run, shape=dataclasses.replace(run.shape, kind="decode",
                                           seq_len=max_len,
                                           global_batch=slots))
        # graph tier (fabric.graph): active runs advanced one round per
        # tick, plus the lazily built multi-token verify step
        self._run_decode = run_decode
        self._kernel_req = kernel
        self._graphs: List[Any] = []
        self._graphs_done: List[Any] = []
        self.graph_invocations = 0
        self._jit_verify = None
        if cache == "paged":
            if num_blocks is None:
                raise ValueError("cache='paged' requires num_blocks=")
            self.block_size, self.chunk = block_size, chunk
            self.num_blocks = num_blocks
            self.max_blocks_per_seq = -(-max_len // block_size)
            if num_blocks < self.max_blocks_per_seq:
                raise ValueError(
                    f"num_blocks={num_blocks} cannot hold one "
                    f"max_len={max_len} request ({self.max_blocks_per_seq} "
                    f"blocks of {block_size})")
            self.bundle = make_paged_serve_step(
                cfg, run_decode, mesh, slots=slots, chunk=chunk,
                num_blocks=num_blocks, block_size=block_size,
                max_blocks_per_seq=self.max_blocks_per_seq, kernel=kernel)
            # resolved attention path ("pallas" | "ref") + per-step
            # live-token fraction: resident tokens / pool token capacity —
            # the occupancy knob the stash-resident kernel's bytes-read win
            # scales with (docs/serving.md)
            self.paged_kernel: str = self.bundle.meta["paged_kernel"]
            self._live_frac_last = 0.0
            self._live_frac_sum = 0.0
            self._live_frac_ticks = 0
            self.peak_blocks_used = 0
            self._step_name = "engine.paged_step"
        elif cache == "recurrent":
            self.chunk = chunk
            self.bundle = make_recurrent_serve_step(
                cfg, run_decode, mesh, slots=slots, chunk=chunk,
                max_len=max_len)
            self._step_name = "engine.recurrent_step"
        else:
            self.bundle = make_serve_step(cfg, run_decode, mesh,
                                          batch_override=slots)
            self._step_name = "engine.decode"
        self._jit_step = jax.jit(self.bundle.fn,
                                 in_shardings=self.bundle.in_shardings,
                                 out_shardings=self.bundle.out_shardings,
                                 donate_argnums=(1,))
        # the cache arg is donated, so pjit refuses to reshard it silently;
        # host-assembled caches (fresh init, prefill scatter) are re-placed
        # onto the step's declared shardings explicitly — a layout op, not
        # a numeric one (multi-device meshes fail without it)
        self._cache_shard = self.bundle.in_shardings[1]

        # --- sequence-state backend (the SequenceState protocol seam) ---
        self._make_state()
        if not self.state.supports_preemption:
            pv = getattr(type(self.policy), "pick_victim", None)
            if pv is not None and pv is not _PolicyBase.pick_victim:
                warnings.warn(
                    f"cache='slots' has no preemption path: "
                    f"{type(self.policy).__name__}.pick_victim will never "
                    "be consulted (admission order still applies); use "
                    "cache='paged' or 'recurrent' for preemption-aware "
                    "scheduling", UserWarning, stacklevel=2)
        _, self.params_shapes, _, _, self.pshard = sharding_ctx(
            cfg, run_decode, mesh)
        self._params_lease = f"{self._step_name}.params"
        self._register_fabric_steps()

    def _make_state(self) -> None:
        """(Re)build the sequence-state backend empty — shared by
        ``__init__`` and ``restart()`` (a restarted replica rejoins with a
        fresh pool, no surviving request state)."""
        template_fn = lambda: jax.jit(
            lambda: model_lib.init_cache(self.cfg, 1, self.max_len))()
        if self.cache_kind == "paged":
            self.state: SequenceState = PagedKVState(self.num_blocks,
                                                     self.block_size)
            self.pool = self.state.pool
        elif self.cache_kind == "recurrent":
            place = lambda t: jax.device_put(t, self._cache_shard)
            self.state = RecurrentState(self.slots, template_fn, place=place)
        else:
            self.state = SlotKVState(self.slots, template_fn)

    # ------------------------------------------------------------------
    # fabric registration / invocation — the one seam
    # ------------------------------------------------------------------

    @property
    def fabric(self):
        """The step bundle's Fabric — the invocation + telemetry surface."""
        return self.bundle.meta.get("fabric")

    @property
    def transport_decisions(self):
        """Auto-mode TransportEstimates recorded while tracing the step
        (delegates to the bundle fabric's decision log)."""
        if self.fabric is not None:
            return [est for _, est in self.fabric.decisions]
        return list(self.bundle.meta.get("transport_log", ()))

    def _register_fabric_steps(self) -> None:
        """Register the serve steps as collectives on the bundle fabric so
        every tick's invocation goes through ``fabric.call`` — the paper's
        one invocation surface. All three placements are real on the tick
        path: ``"local"`` runs against receiver-resident weights,
        ``"injected"`` acquires the step's rFaaS params lease every tick
        (the first acquire is the injection — a miss that ships the weight
        tree; later ticks hit warm), ``"auto"`` consults the cost model
        per tick (``_resolve_auto``). Every branch runs the same compiled
        step on the same mesh, so placement never changes the math — only
        where the weights are accounted as living. The resolved placement
        per step lands in ``metrics()["fabric"]["placements"]``."""
        fabric = self.fabric
        if fabric is None:              # pragma: no cover - bundles always
            return                      # carry a fabric; kept as a guard
        lease_name = self._params_lease

        def invoke_step(payload, state, placement):
            placement = self._guarded_placement(
                self._step_name, self._tick_payload_bytes(payload[1:]),
                state, placement)
            if placement == "injected":
                fabric.lease(lease_name, jax.tree.leaves(state))
            self._placements[self._step_name] = placement
            return self._jit_step(state, *payload)

        fabric.register_collective(self._step_name, invoke_step,
                                   placements=("local", "injected", "auto"))
        self._placements[self._step_name] = self.placement
        if self.cache_kind == "slots":
            def invoke_prefill(payload, state, placement):
                placement = self._guarded_placement(
                    "engine.prefill", self._tick_payload_bytes((payload,)),
                    state, placement)
                if placement == "injected":
                    fabric.lease(lease_name, jax.tree.leaves(state))
                self._placements["engine.prefill"] = placement
                one_cache = model_lib.init_cache(self.cfg, 1, self.max_len)
                return model_lib.forward(self.cfg, state, payload,
                                         cache=one_cache)

            fabric.register_collective(
                "engine.prefill", invoke_prefill,
                placements=("local", "injected", "auto"))
            self._placements["engine.prefill"] = self.placement

    def _step_call(self, *args):
        """One tick's compiled-step invocation, routed through the fabric
        at this engine's configured placement."""
        fabric = self.fabric
        if fabric is None:              # pragma: no cover - guard only
            return self._jit_step(self.params, *args)
        return fabric.call(self._step_name, args, state=self.params,
                           placement=self.placement)

    def _session_step_call(self, *args, placement: Optional[str] = None):
        """A graph session's step invocation — same fabric-registered
        step as ``tick``, but at the session's own placement."""
        self._check_alive("session step")
        return self.fabric.call(self._step_name, args, state=self.params,
                                placement=placement or self.placement)

    def ensure_verify_step(self) -> None:
        """Build + register the multi-token verify step lazily (paged
        only): the same serve step compiled with ``emit="all"`` — greedy
        token at *every* fed position instead of the last — which is what
        a speculation round reads to accept/reject k candidates in one
        invocation. Same geometry, same kernel, same cache layout; it
        shares the decode step's params lease and placement guard, and
        shows up in ``metrics()`` as ``engine.paged_verify``."""
        if self.cache_kind != "paged":
            raise ValueError(
                f"the verify step rides the paged chunked-prefill shape; "
                f"engine {self.engine_id} has cache={self.cache_kind!r}")
        if self._jit_verify is not None:
            return
        bundle = make_paged_serve_step(
            self.cfg, self._run_decode, self.mesh, slots=self.slots,
            chunk=self.chunk, num_blocks=self.num_blocks,
            block_size=self.block_size,
            max_blocks_per_seq=self.max_blocks_per_seq,
            kernel=self._kernel_req, emit="all")
        self._jit_verify = jax.jit(bundle.fn,
                                   in_shardings=bundle.in_shardings,
                                   out_shardings=bundle.out_shardings,
                                   donate_argnums=(1,))
        fabric = self.fabric
        if fabric is None:              # pragma: no cover - guard only
            return
        lease_name = self._params_lease

        def invoke_verify(payload, state, placement):
            placement = self._guarded_placement(
                "engine.paged_verify",
                self._tick_payload_bytes(payload[1:]), state, placement)
            if placement == "injected":
                fabric.lease(lease_name, jax.tree.leaves(state))
            self._placements["engine.paged_verify"] = placement
            return self._jit_verify(state, *payload)

        fabric.register_collective("engine.paged_verify", invoke_verify,
                                   placements=("local", "injected", "auto"))
        self._placements["engine.paged_verify"] = self.placement

    def _verify_call(self, *args, placement: Optional[str] = None):
        """One verify-step invocation through the fabric (lazily building
        the step on first use)."""
        self._check_alive("verify step")
        self.ensure_verify_step()
        if self.fabric is None:         # pragma: no cover - guard only
            return self._jit_verify(self.params, *args)
        return self.fabric.call("engine.paged_verify", args,
                                state=self.params,
                                placement=placement or self.placement)

    def lower_step(self):
        """The tick's compiled step, lowered for its abstract inputs (no
        weights needed): ``.compile().as_text()`` shows what the chip runs
        — e.g. whether the paged kernel is a Mosaic ``tpu_custom_call``."""
        return self._jit_step.lower(*self.bundle.abstract_inputs)

    # -- placement resolution (the cost-model side of placement="auto") ----

    def _params_nbytes(self) -> int:
        if self._params_nbytes_memo is None and self.params is not None:
            self._params_nbytes_memo = sum(
                int(leaf.size) * leaf.dtype.itemsize
                for leaf in jax.tree.leaves(self.params)
                if hasattr(leaf, "dtype"))
        return self._params_nbytes_memo or 0

    @staticmethod
    def _tick_payload_bytes(payload) -> int:
        """Wire bytes of one tick's scheduler arrays (tokens / tables /
        starts / n_valid) — what placement='local' ships to wherever the
        weights live. The resident cache is excluded: sequence state stays
        put under either placement (migration, not placement, moves it)."""
        return sum(int(a.size) * a.dtype.itemsize
                   for a in jax.tree.leaves(payload)
                   if hasattr(a, "dtype"))

    def _lease_warm(self, state) -> bool:
        """True when a live params lease holds exactly these arrays (the
        ``is``-keyed hit condition of ``fabric.leases``)."""
        lease = self.fabric.leases.get(self._params_lease)
        leaves = jax.tree.leaves(state)
        return bool(lease is not None and lease.live
                    and len(lease.key) == len(leaves)
                    and all(a is b for a, b in zip(lease.key, leaves)))

    def _resolve_auto(self, name: str, payload_bytes: int, state) -> str:
        """Resolve placement='auto' for one tick: injected while the
        params lease is warm (the weights already live with the executor —
        reuse ships nothing), local while it is cold (a first injection
        would ship the whole weight tree for one tick's worth of payload).
        ``inject_params`` pre-warms the lease, so router-managed replicas
        resolve injected from their first tick. The estimate is recorded
        on the fabric's decision log either way."""
        warm = self._lease_warm(state)
        injected_bytes = 0 if warm else self._params_nbytes()
        est = TransportEstimate(
            local_bytes=payload_bytes, injected_bytes=injected_bytes,
            common_bytes=0,
            chosen="injected" if injected_bytes <= payload_bytes else "local",
            n_tokens_per_tp_rank=0, capacity=0)
        self.fabric.record_decision(name, est)
        return est.chosen

    def _guarded_placement(self, name: str, payload_bytes: int, state,
                           placement: str) -> str:
        """Resolve ``"auto"`` and close the lease-expiry race: the params
        lease can expire (TTL, eviction, an injected storm) *between*
        placement resolution and step execution — ``fault_hook`` fires in
        exactly that window. An auto resolution of ``injected`` was
        premised on warm reuse shipping zero bytes, so if the lease went
        cold underneath it the call falls back to ``local`` (counted in
        ``lease_fallbacks``) instead of silently re-shipping the whole
        weight tree — or erroring. An *explicit* ``placement="injected"``
        is untouched: re-acquiring on a cold lease IS the injection."""
        requested = placement
        if placement == "auto":
            placement = self._resolve_auto(name, payload_bytes, state)
        if self.fault_hook is not None:
            self.fault_hook(name)
        if (requested == "auto" and placement == "injected"
                and not self._lease_warm(state)):
            self.lease_fallbacks += 1
            placement = "local"
        return placement

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    def load_params(self, params: Optional[PyTree] = None) -> None:
        """Install model weights (init randomly from ``run.seed`` when none
        given). A given tree is placed onto this engine's own parameter
        shardings: a tree shared by replicas on different devices lands on
        each replica's device, not on whichever device built it."""
        if params is None:
            # in the dtype the step is built for (serving deploys bf16,
            # runtime.steps.sharding_ctx): the same values the step would
            # cast an f32 tree to, at half the HBM
            dtype = jax.tree.leaves(self.params_shapes)[0].dtype
            init = jax.jit(lambda k: model_lib.init_params(
                self.cfg, k, param_dtype=dtype)[0],
                out_shardings=self.pshard)
            params = init(jax.random.PRNGKey(self.run.seed))
        else:
            params = jax.device_put(params, self.pshard)
        self.params = params
        self._params_nbytes_memo = None
        self.cache = self._fresh_cache()

    def inject_params(self, params: Optional[PyTree] = None) -> None:
        """Install weights *and* warm the step's params lease — the
        executor side of ``placement="injected"``/``"auto"``: a router
        shipping one shared weight tree to N replicas calls this instead
        of ``load_params``, after which ``placement="auto"`` resolves to
        injected (warm reuse) from the replica's first tick and the
        injection itself is visible as the lease's one miss."""
        self.load_params(params)
        if self.fabric is not None:
            self.fabric.lease(self._params_lease,
                              jax.tree.leaves(self.params))

    # -- failure lifecycle (the replica side of cluster failover) ---------

    @property
    def alive(self) -> bool:
        return self.failed_reason is None

    def fail(self, reason: str = "injected failure") -> None:
        """Put the engine into the failed state: every subsequent tick /
        submit / export / import / snapshot raises ``EngineFailedError``
        until ``restart()``. Host-side bookkeeping (metrics, completed
        requests) stays readable — a dead process's logs survive it."""
        self.failed_reason = reason

    def restart(self) -> None:
        """Simulate a process restart: clear the failure flag and abandon
        ALL request state — queue, slots, pool blocks, stream handles —
        so the replica rejoins empty (a real restarted process holds no
        sequence state; the router has already recovered its requests
        elsewhere). Params and compiled steps survive: they are
        process-image, not request state."""
        self.failed_reason = None
        for entry in self._entries_everywhere():
            entry.handle = None
        self.queue.clear()
        self.slot_entry = [None] * self.slots
        self._pending_pump.clear()
        self._graphs.clear()            # sessions die with the pool
        self._make_state()
        if self.params is not None:
            self.cache = self._fresh_cache()

    def _check_alive(self, what: str) -> None:
        if self.failed_reason is not None:
            raise EngineFailedError(
                self.engine_id, f"{self.failed_reason} (refusing {what})")

    def _fresh_cache(self) -> PyTree:
        if self.cache_kind == "paged":
            fresh = jax.jit(lambda: model_lib.init_paged_cache(
                self.cfg, self.num_blocks, self.block_size))()
        else:
            fresh = jax.jit(lambda: model_lib.init_cache(
                self.cfg, self.slots, self.max_len))()
        return jax.device_put(fresh, self._cache_shard)

    def pending(self) -> bool:
        """True while any request is queued or occupying a slot, or any
        graph run is still looping."""
        return bool(self.queue
                    or any(e is not None for e in self.slot_entry)
                    or any(not run.done for run in self._graphs))

    def run_until_drained(self, max_ticks: int = 10_000) -> List[Request]:
        """Serve until queue + slots drain; returns completed requests.
        (Streaming clients can instead pull ``handle.tokens()``.)"""
        while self.pending() and self.ticks < max_ticks:
            self.tick()
        return self.completed

    # ------------------------------------------------------------------
    # request plumbing
    # ------------------------------------------------------------------

    def submit(self, req: Request) -> RequestHandle:
        """Queue a request; returns its streaming ``RequestHandle``."""
        self._check_alive("submit")
        # reject up front what could never finish: past this check a
        # request's sequence always fits the backend's capacity model
        # (for paged: max_blocks_per_seq blocks, so the block table row
        # cannot overflow and a lone request never starves)
        msg = self.state.validate(len(req.prompt), req.max_new_tokens,
                                  self.max_len)
        if msg:
            raise ValueError(f"request {req.rid}: {msg}")
        req.arrival_tick = self.ticks
        entry = _Entry(req=req, submit_time=time.perf_counter(),
                       arrival_seq=self._submit_counter,
                       prompt_tokens=[int(t) for t in req.prompt])
        self._submit_counter += 1
        entry.handle = RequestHandle(self, req)
        self.queue.append(entry)
        return entry.handle

    def submit_graph(self, spec, inputs, *, loop_until=None,
                     max_rounds: int = 256, resolve=None,
                     on_node_error=None):
        """Queue a ``fabric.graph`` run; returns its streaming
        ``GraphHandle``. The scheduler admits the run's *node
        invocations*: each ``tick`` advances every active graph one
        round (all nodes once, topo order) alongside the request rows,
        node outputs land as warm leases on this engine's fabric
        (``graph/<gid>/<node>``), and ``handle.tokens()`` drives
        ``tick()`` exactly like ``RequestHandle.tokens()`` does. Graphs
        that loop (``loop_until``) keep their round cadence: one
        speculation round per tick for the draft/verify graph."""
        self._check_alive("submit_graph")
        from repro.fabric.graph.executor import GraphRun
        run = GraphRun(spec, inputs, fabric=self.fabric,
                       loop_until=loop_until, max_rounds=max_rounds,
                       resolve=resolve, on_node_error=on_node_error)
        self._graphs.append(run)
        return run.handle._bind(self)

    def _tick_graphs(self) -> int:
        """Advance every active graph run one round; returns the number
        of node invocations fired."""
        fired = 0
        for run in list(self._graphs):
            if not run.done:
                fired += run.advance()
            if run.done:
                self._graphs.remove(run)
                self._graphs_done.append(run)
        self.graph_invocations += fired
        return fired

    def _sched_state(self, block_budget: Optional[int]) -> SchedulerState:
        return SchedulerState(
            tick=self.ticks,
            free_slots=sum(e is None for e in self.slot_entry),
            block_budget=block_budget,
            blocks_needed=self.state.units_needed,
            capacity=self.state.capacity())

    def _stamp_admitted(self, entry: _Entry) -> None:
        if entry.admit_seq < 0:
            entry.admit_seq = self._admit_counter
            self._admit_counter += 1
            self.admission_log.append(entry.req.rid)

    def _emit(self, entry: _Entry, tok: int) -> None:
        """Append one generated token + TTFT stamps; streaming delivery is
        deferred to ``_flush_streams`` at the end of the tick so a raising
        client callback can never abort the engine's own bookkeeping
        mid-loop (which would silently drop co-scheduled tokens)."""
        entry.req.out_tokens.append(tok)
        if len(entry.req.out_tokens) == 1:
            entry.first_token_time = time.perf_counter()
            entry.first_token_tick = self.ticks
        if entry.handle is not None:
            self._pending_pump.append(entry)

    def _flush_streams(self) -> None:
        """Deliver this tick's tokens to stream callbacks. Runs after all
        token appends/completions; a raising callback propagates to the
        tick() caller but leaves the engine consistent — undelivered
        entries stay queued and flush on the next tick."""
        while self._pending_pump:
            entry = self._pending_pump.pop(0)
            if entry.handle is not None:
                entry.handle._pump()

    def _complete(self, slot: int, entry: _Entry) -> None:
        entry.req.done = True
        self.state.release(entry)
        self.completed.append(entry.req)
        self._finished.append(entry)
        self.slot_entry[slot] = None

    def _entries_everywhere(self) -> List[_Entry]:
        out = list(self.queue) + [e for e in self.slot_entry if e is not None]
        out.extend(self._finished)
        return out

    # ------------------------------------------------------------------
    # tick — one admit/step/complete round, backend-dispatched
    # ------------------------------------------------------------------

    def tick(self) -> int:
        """Admit + advance every active request one step, then every
        active graph run one round. Returns rows advanced plus node
        invocations fired."""
        self._check_alive("tick")
        if self.cache_kind == "slots":
            advanced = self._tick_slots()
        else:
            advanced = self._tick_chunked()
        if self._graphs:
            advanced += self._tick_graphs()
        return advanced

    # -- slots (fixed-slot contiguous cache) backend ----------------------

    def _admit_slots(self) -> None:
        for slot in range(self.slots):
            if self.slot_entry[slot] is not None or not self.queue:
                continue
            idx = self.policy.admit(self.queue, self._sched_state(None))
            if idx is None:
                return
            entry = self.queue.pop(idx)
            self._stamp_admitted(entry)
            if entry.inbound is not None:
                # migrated-in: the serialized row replaces the prefill
                # forward (its tokens are already absorbed); the next
                # decode tick feeds out_tokens[-1] like any resident row
                self.slot_entry[slot] = entry
                self._restore_inbound(entry, slot)
            else:
                self._prefill_slot(slot, entry)

    def _prefill_slot(self, slot: int, entry: _Entry) -> None:
        """Run the prompt through the model, writing this slot's cache rows.

        Single-slot prefill through the fabric-registered ``engine.prefill``
        step: a (1, L) forward with a fresh length-``max_len`` cache, then
        scatter the slot row into the live batched cache.
        """
        req = entry.req
        # Recovery recompute (failover rebuilt this request from prompt +
        # already-delivered tokens, no state bytes): re-run everything
        # known except the newest token — the next decode tick feeds it
        # back exactly like any resident row — and emit nothing, because
        # every known token was already delivered upstream. Fresh requests
        # (no out_tokens) keep the original prompt-only + argmax path.
        known = entry.seq()
        tokens = known[:-1] if req.out_tokens else known
        prompt = jnp.asarray(tokens, jnp.int32)[None, :]
        fabric = self.fabric
        if fabric is None:              # pragma: no cover - guard only
            one_cache = model_lib.init_cache(self.cfg, 1, self.max_len)
            logits, filled, _ = model_lib.forward(
                self.cfg, self.params, prompt, cache=one_cache)
        else:
            logits, filled, _ = fabric.call("engine.prefill", prompt,
                                            state=self.params,
                                            placement="local")
        if not req.out_tokens:
            self._emit(entry, int(jnp.argmax(logits[0, -1, :])))

        def scatter(live, one):
            # Cache leaves may carry a leading layer-stack dim
            # ((repeats, B, ...) for scanned groups), so the batch axis is
            # located structurally: the first axis where the live leaf has
            # ``slots`` extent, the one-row prefill leaf has extent 1, and
            # every leading dim matches. (Matching on shape[:1] mistook the
            # layer-stack dim for batch: slots=1 silently dropped the whole
            # prefill and slots==repeats scattered layers as slots.)
            if getattr(live, "ndim", 0) == 0:
                return live
            for ax in range(live.ndim):
                if (live.shape[ax] == self.slots and one.shape[ax] == 1
                        and live.shape[:ax] == one.shape[:ax]):
                    idx = (slice(None),) * ax + (slot,)
                    return live.at[idx].set(jnp.take(one, 0, axis=ax))
            return live

        # lengths differ per slot; keep the max (cache length is per-batch
        # scalar — decode masks by absolute position so overshoot is safe)
        new_groups = jax.tree.map(scatter, self.cache["groups"],
                                  filled["groups"])
        self.cache = jax.device_put(
            {"length": jnp.maximum(self.cache["length"], filled["length"]),
             "groups": new_groups}, self._cache_shard)
        self.slot_entry[slot] = entry

    def _tick_slots(self) -> int:
        self._admit_slots()
        active = [i for i, e in enumerate(self.slot_entry) if e is not None]
        if not active:
            self._flush_streams()       # leftovers from a raising flush
            return 0
        self.peak_active = max(self.peak_active, len(active))
        tokens = np.zeros((self.slots, 1), np.int32)
        for i, e in enumerate(self.slot_entry):
            if e is not None:
                tokens[i, 0] = e.req.out_tokens[-1]
        args = [self.cache, jnp.asarray(tokens)]
        if self.cfg.attention is not None and self.cfg.attention.mrope:
            pos = np.broadcast_to(
                np.asarray(self.cache["length"])[None, None],
                (3, self.slots, 1)).astype(np.int32)
            args.append(jnp.asarray(pos))
        next_tok, self.cache = self._step_call(*args)
        next_np = np.asarray(next_tok)
        for i in active:
            e = self.slot_entry[i]
            tok = int(next_np[i, 0])
            self._emit(e, tok)
            if (len(e.req.out_tokens) >= e.req.max_new_tokens
                    or (self.eos_id is not None and tok == self.eos_id)):
                self._complete(i, e)
        self.ticks += 1
        self._flush_streams()
        return len(active)

    # -- chunked (paged / recurrent) backends -----------------------------

    def _blocks_for(self, tokens: int) -> int:
        return -(-tokens // self.block_size)

    def _admit_chunked(self) -> None:
        """Policy-gated admission: the policy picks the next queued entry;
        it admits only when a slot is free AND the backend can hold its
        whole resident prefix plus one decode token. ``budget`` tracks the
        capacity units already promised to entries admitted in this same
        call — their allocation happens later in tick phase A, so reading
        ``capacity().free_units`` alone would over-commit the pool and
        trigger spurious preemptions of just-admitted requests. Backends
        whose capacity is not consumable (``free_units`` None) gate on
        free slots alone."""
        budget = self.state.capacity().free_units
        while self.queue:
            free_slots = [i for i, e in enumerate(self.slot_entry)
                          if e is None]
            if not free_slots:
                return
            state = self._sched_state(budget)
            idx = self.policy.admit(self.queue, state)
            if idx is None:
                return                  # policy head blocked => wait
            entry = self.queue.pop(idx)
            if budget is not None:
                # debit what the policy *reserved* (its budget() — >= the
                # exact need, e.g. headroom-reserving policies), never less
                # than the real need, so the round ledger cannot over-commit
                budget -= max(self.policy.budget(entry, state),
                              self.state.units_needed(entry))
            self._stamp_admitted(entry)
            slot = free_slots[0]
            self.slot_entry[slot] = entry
            self.cache = self.state.init(entry, self.cache, slot)
            if entry.inbound is not None:
                self._restore_inbound(entry, slot)

    def _restore_inbound(self, entry: _Entry, slot: int) -> None:
        """Absorb a migrated-in request's serialized state into ``slot``
        (the admission side of ``import_request``). Paged entries first
        re-acquire blocks covering the resident prefix — growth may
        preempt a victim, exactly as a native request's growth would; the
        restored rows then land in this pool's own blocks. After this the
        entry is indistinguishable from one that prefilled here: chunked
        backends resume at ``entry.pos``, slots decode from
        ``out_tokens[-1]``."""
        if self.cache_kind == "paged":
            self._ensure_capacity(entry, max(entry.pos, 1))
        self.cache = jax.device_put(
            self.state.restore(entry, self.cache, slot, entry.inbound),
            self._cache_shard)
        entry.inbound = None

    def _preempt(self, victim: _Entry) -> None:
        """Evict the victim through the backend and requeue it in admission
        order: before every never-admitted entry and every
        previously-preempted entry with a younger admit stamp. (Plain
        front-insertion breaks FIFO when two preemptions land out of stamp
        order — e.g. the youngest running entry grows and evicts a
        middle-aged one, then an older entry evicts the youngest.)
        Generated tokens are kept. What eviction *costs* is the backend's
        call: paged releases blocks and resets ``pos`` (re-admission
        re-prefills — recompute), recurrent snapshots the slot's state and
        keeps ``pos`` (re-admission resumes — never a recompute), slots
        raises (no preemption path). Reordering policies re-decide at the
        next admission anyway, so the stamp-ordered insert is
        policy-neutral."""
        slot = self.slot_entry.index(victim)
        self.cache = self.state.evict(victim, self.cache, slot)
        victim.preemptions += 1
        self.preempt_count += 1
        self.slot_entry[slot] = None
        at = next((i for i, e in enumerate(self.queue)
                   if e.admit_seq < 0 or e.admit_seq > victim.admit_seq),
                  len(self.queue))
        self.queue.insert(at, victim)

    def _ensure_capacity(self, entry: _Entry, upto_tokens: int) -> None:
        """Grow the entry's state to cover ``upto_tokens``, preempting the
        policy's victim among the other running requests whenever the
        backend reports exhaustion."""
        while not self.state.grow(entry, upto_tokens):
            running = [e for e in self.slot_entry
                       if e is not None and e is not entry]
            victim = self.policy.pick_victim(running, self._sched_state(0))
            if victim is None:
                # unreachable given the num_blocks >= max_blocks_per_seq
                # init check: a lone request always fits
                raise RuntimeError("block pool exhausted by a single request")
            self._preempt(victim)

    def _tick_chunked(self) -> int:
        self._admit_chunked()
        paged = self.cache_kind == "paged"

        # phase A: chunk sizing + capacity growth (may preempt victims,
        # including entries already scheduled earlier in this loop).
        # seq is materialized once per entry per tick — it is O(seq_len).
        sched: List[Tuple[int, _Entry, int, List[int]]] = []
        for slot in range(self.slots):
            entry = self.slot_entry[slot]
            if entry is None:
                continue
            seq = entry.seq()
            n = min(self.chunk, len(seq) - entry.pos)
            self._ensure_capacity(entry, entry.pos + n)
            sched.append((slot, entry, n, seq))
        sched = [item for item in sched if self.slot_entry[item[0]] is item[1]]
        # the tick counts even when nothing is schedulable, so
        # run_until_drained's max_ticks stays a hard bound (a queue head
        # that can never admit must not spin forever)
        self.ticks += 1
        if not sched:
            self._flush_streams()       # leftovers from a raising flush
            return 0
        self.peak_active = max(self.peak_active, len(sched))
        if paged:
            self.peak_blocks_used = max(self.peak_blocks_used,
                                        self.pool.used_blocks)
            # tokens resident after this step's writes / pool token capacity
            live = sum(entry.pos + n for _, entry, n, _ in sched)
            self._live_frac_last = live / (self.num_blocks * self.block_size)
            self._live_frac_sum += self._live_frac_last
            self._live_frac_ticks += 1

        # phase B: build the fixed-shape step inputs
        tokens = np.zeros((self.slots, self.chunk), np.int32)
        starts = np.zeros((self.slots,), np.int32)
        n_valid = np.zeros((self.slots,), np.int32)
        if paged:
            tables = np.full((self.slots, self.max_blocks_per_seq), -1,
                             np.int32)
        for slot, entry, n, seq in sched:
            tokens[slot, :n] = seq[entry.pos:entry.pos + n]
            if paged:
                tables[slot, :len(entry.blocks)] = entry.blocks
            starts[slot] = entry.pos
            n_valid[slot] = n

        args = [self.cache, jnp.asarray(tokens)]
        if paged:
            args.append(jnp.asarray(tables))
        args.extend([jnp.asarray(starts), jnp.asarray(n_valid)])
        next_tok, self.cache = self._step_call(*args)
        next_np = np.asarray(next_tok)

        for slot, entry, n, seq in sched:
            known = len(seq)
            entry.pos += n
            self.state.append(entry, n)
            if entry.pos < known:
                continue                 # mid-prefill: output discarded
            tok = int(next_np[slot])
            self._emit(entry, tok)
            if (len(entry.req.out_tokens) >= entry.req.max_new_tokens
                    or (self.eos_id is not None and tok == self.eos_id)):
                self._complete(slot, entry)

        self._flush_streams()
        return len(sched)

    def preempt(self, rid: int) -> None:
        """Evict a running request by id through the backend's preemption
        path and requeue it (admission-ordered). Paged requeues recompute
        the prefix; recurrent resumes from its state snapshot; slots
        raises — it has no preemption path."""
        for entry in self.slot_entry:
            if entry is not None and entry.req.rid == rid:
                self._preempt(entry)
                return
        raise KeyError(f"request {rid} is not running in any slot")

    # ------------------------------------------------------------------
    # live migration — export/import of in-flight entries (ROADMAP item 3)
    # ------------------------------------------------------------------

    def export_request(self, rid: int) -> MigrationTicket:
        """Detach request ``rid`` — queued or running — into a
        position-independent ``MigrationTicket`` and release everything it
        held here (slot, blocks, snapshot, stream handle). Called between
        ticks by a router; the ticket restores on any engine with the same
        model and ``cache_kind`` via ``import_request``, resuming with
        greedy output bitwise identical to never having moved. Raises
        ``KeyError`` for unknown or finished rids (a finished request has
        nothing left to move)."""
        self._check_alive("export_request")
        for slot in range(self.slots):
            entry = self.slot_entry[slot]
            if entry is not None and entry.req.rid == rid:
                return self._export_entry(entry, slot)
        for i, entry in enumerate(self.queue):
            if entry.req.rid == rid:
                self.queue.pop(i)
                return self._export_entry(entry, None)
        raise KeyError(
            f"request {rid} is not queued or running on {self.engine_id} "
            f"(finished requests cannot migrate)")

    def _export_entry(self, entry: _Entry,
                      slot: Optional[int]) -> MigrationTicket:
        req = entry.req
        buf: Optional[bytes] = None
        pos = 0
        if slot is not None:
            if self.cache_kind == "slots":
                # resident: whole prompt + every generated token except
                # the newest (it has not been fed back through the step)
                buf = self.state.serialize(entry, self.cache, slot)
                pos = (len(entry.prompt_tokens)
                       + max(0, len(req.out_tokens) - 1))
            elif entry.pos > 0:
                buf = self.state.serialize(entry, self.cache, slot)
                pos = entry.pos
            self.slot_entry[slot] = None
        elif entry.inbound is not None:
            # migrated in but re-exported before admission absorbed the
            # buffer: forward it verbatim (dropping it would silently
            # demote a warm handoff to a from-scratch recompute)
            buf = entry.inbound
            pos = entry.pos
        elif self.cache_kind == "recurrent" and entry.snapshot is not None:
            # preempted-and-requeued: the host snapshot IS the state
            buf = state_to_bytes(entry.snapshot)
            pos = entry.pos
        self.state.release(entry)
        ticket = self._ticket_for(entry, buf, pos)
        # detach the local stream: the source-side handle must not see
        # tokens the target produces (the router rebinds its own handle)
        entry.handle = None
        self._pending_pump = [e for e in self._pending_pump if e is not entry]
        self.migrations_out += 1
        return ticket

    def _ticket_for(self, entry: _Entry, buf: Optional[bytes],
                    pos: int) -> MigrationTicket:
        req = entry.req
        return MigrationTicket(
            rid=req.rid, cache_kind=self.cache_kind, priority=req.priority,
            max_new_tokens=req.max_new_tokens,
            prompt=list(entry.prompt_tokens),
            out_tokens=list(req.out_tokens), pos=pos, state=buf)

    def snapshot_request(self, rid: int) -> MigrationTicket:
        """Non-destructive twin of ``export_request``: serialize ``rid``'s
        sequence state into a ``MigrationTicket`` *without* releasing
        anything — the request keeps running here, slot and blocks intact.
        A router takes these periodically (its snapshot cadence) so that
        when this replica dies, the request restores on a peer from the
        last snapshot — regenerating only the tokens emitted since — in
        place of a full from-scratch recompute. Raises ``KeyError`` for
        unknown or finished rids."""
        self._check_alive("snapshot_request")
        for slot in range(self.slots):
            entry = self.slot_entry[slot]
            if entry is None or entry.req.rid != rid:
                continue
            buf: Optional[bytes] = None
            pos = 0
            if self.cache_kind == "slots":
                # same coverage rule as export: everything but the newest
                # token (not yet fed back through the step)
                buf = self.state.serialize(entry, self.cache, slot)
                pos = (len(entry.prompt_tokens)
                       + max(0, len(entry.req.out_tokens) - 1))
            elif entry.pos > 0:
                buf = self.state.serialize(entry, self.cache, slot)
                pos = entry.pos
            return self._ticket_for(entry, buf, pos)
        for entry in self.queue:
            if entry.req.rid != rid:
                continue
            if entry.inbound is not None:
                return self._ticket_for(entry, entry.inbound, entry.pos)
            if (self.cache_kind == "recurrent"
                    and entry.snapshot is not None):
                return self._ticket_for(entry, state_to_bytes(entry.snapshot),
                                        entry.pos)
            return self._ticket_for(entry, None, 0)
        raise KeyError(
            f"request {rid} is not queued or running on {self.engine_id} "
            f"(finished requests have no state to snapshot)")

    def import_request(self, ticket: MigrationTicket) -> RequestHandle:
        """Admit a migrated request. The rebuilt entry enters the queue
        like a fresh submit (policies see its original priority); its
        serialized state — when the ticket carries one — is absorbed at
        admission by ``_restore_inbound`` instead of a prefill, so
        decoding resumes at token ``pos`` with no recompute (paged resumes
        even mid-chunked-prefill: ``pos`` is a chunk boundary and the
        chunk policy is deterministic). Tickets from a different backend
        are rejected: the state bytes are only meaningful to their own
        ``cache_kind``."""
        self._check_alive("import_request")
        if ticket.cache_kind != self.cache_kind:
            raise ValueError(
                f"cannot import a cache_kind={ticket.cache_kind!r} ticket "
                f"into {self.engine_id} (cache_kind={self.cache_kind!r}): "
                f"sequence-state bytes do not convert across backends")
        prompt = np.asarray(ticket.prompt, np.int32)
        msg = self.state.validate(len(ticket.prompt), ticket.max_new_tokens,
                                  self.max_len)
        if msg:
            raise ValueError(f"request {ticket.rid}: {msg}")
        req = Request(rid=ticket.rid, prompt=prompt,
                      max_new_tokens=ticket.max_new_tokens,
                      priority=ticket.priority,
                      out_tokens=list(ticket.out_tokens))
        req.arrival_tick = self.ticks
        entry = _Entry(req=req, submit_time=time.perf_counter(),
                       arrival_seq=self._submit_counter,
                       prompt_tokens=list(ticket.prompt))
        self._submit_counter += 1
        if ticket.state is not None:
            entry.inbound = ticket.state
            entry.pos = ticket.pos
        entry.handle = RequestHandle(self, req)
        self.queue.append(entry)
        self.migrations_in += 1
        return entry.handle

    # ------------------------------------------------------------------
    # metrics — one unified schema for both backends
    # ------------------------------------------------------------------

    def _request_records(self) -> List[Dict[str, Any]]:
        """Per-request metrics (submit order): priority/arrival/TTFT —
        previously reconstructible only from server internals."""
        recs = []
        for e in sorted(self._entries_everywhere(),
                        key=lambda e: e.arrival_seq):
            r = e.req
            recs.append({
                "rid": r.rid,
                "priority": r.priority,
                "arrival_tick": r.arrival_tick,
                "admitted": e.admit_seq >= 0,
                "first_token_tick": e.first_token_tick,
                "ttft_s": (e.first_token_time - e.submit_time
                           if e.first_token_time is not None else None),
                "tokens": len(r.out_tokens),
                "preemptions": e.preemptions,
                "done": r.done,
            })
        return recs

    def _transport_metrics(self) -> Dict[str, Any]:
        """Transport telemetry block of ``metrics()`` — delegates to the
        bundle fabric (the ``fabric`` key carries its full ``metrics()``
        dict plus the resolved placement of every engine-registered step);
        the two legacy keys are kept for pre-Fabric consumers."""
        out: Dict[str, Any] = {
            "transport_decisions": [est.describe()
                                    for est in self.transport_decisions],
            "transport_telemetry": transport_lib.get_telemetry().summary(),
        }
        if self.fabric is not None:
            fm = self.fabric.metrics()
            fm["placements"] = dict(self._placements)
            fm["lease_fallbacks"] = self.lease_fallbacks
            out["fabric"] = fm
        return out

    def metrics(self) -> Dict[str, Any]:
        """Unified engine telemetry snapshot (JSON-friendly).

        One schema for both cache backends: scheduler progress, per-request
        records (``requests``), TTFT distribution, preemption counters, and
        the fabric/transport block; the paged backend adds its pool keys
        (same names the legacy paged server reported). docs/engine.md
        documents every key.
        """
        done = [e for e in self._entries_everywhere() if e.req.done]
        ttfts = sorted(e.first_token_time - e.submit_time
                       for e in done if e.first_token_time is not None)
        out: Dict[str, Any] = {
            "engine": {
                # engine_id first: the merge key multi-replica metric
                # consumers (cluster.metrics()) disambiguate replicas by
                "engine_id": self.engine_id,
                "cache": self.cache_kind,
                "scheduler": self.policy.name,
                "slots": self.slots,
                "max_len": self.max_len,
                "placement": self.placement,
                "failed_reason": self.failed_reason,
            },
            "ticks": self.ticks,
            "active_slots": sum(e is not None for e in self.slot_entry),
            "peak_active_slots": self.peak_active,
            "queued": len(self.queue),
            "completed": len(self.completed),
            "preemptions": self.preempt_count,
            "migrations": {"in": self.migrations_in,
                           "out": self.migrations_out},
            "ttft_s": ttfts,
            "requests": self._request_records(),
            **self._transport_metrics(),
        }
        if self._graphs or self._graphs_done:
            out["graphs"] = {
                "active": len(self._graphs),
                "completed": len(self._graphs_done),
                "node_invocations": self.graph_invocations,
                "runs": [run.metrics()
                         for run in self._graphs + self._graphs_done],
            }
        if self.cache_kind == "paged":
            out.update({
                "paged_kernel": self.paged_kernel,
                "live_token_fraction": self._live_frac_last,
                "live_token_fraction_mean": (
                    self._live_frac_sum / self._live_frac_ticks
                    if self._live_frac_ticks else 0.0),
                "num_blocks": self.num_blocks,
                "block_size": self.block_size,
                "chunk": self.chunk,
                "free_blocks": self.pool.free_blocks,
                "used_blocks": self.pool.used_blocks,
                "peak_used_blocks": self.peak_blocks_used,
                "occupancy": self.pool.used_blocks / max(1, self.num_blocks),
            })
        elif self.cache_kind == "recurrent":
            out.update({"chunk": self.chunk, **self.state.metrics()})
        return out
