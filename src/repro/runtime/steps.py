"""Jittable step functions: train_step / prefill_step / serve_step.

One factory per step kind. Each returns ``(fn, in_shardings, out_shardings,
abstract_inputs)`` so ``launch.dryrun`` can ``jax.jit(fn, in_shardings=...,
out_shardings=...).lower(*abstract_inputs).compile()`` with zero allocation,
and the trainer/server can call the same jitted function with real arrays.

Every bundle owns a ``repro.fabric.Fabric`` bound to its mesh
(``bundle.meta["fabric"]``): MoE architectures get the Two-Chains jam
transport registered on it when the mesh has a >1 tensor axis (otherwise
the single-device oracle runs), and Trainer/Server delegate their
transport telemetry to ``fabric.metrics()``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, OptimizerConfig, RunConfig, ShapeConfig
from repro.data.synthetic import batch_shapes
from repro.fabric import Fabric
from repro.kernels import paged_attention as paged_attention_lib
from repro.models import blocks as blocks_mod
from repro.models import model as model_lib
from repro.models.kvcache import PagedLayout, RecurrentLayout
from repro.optim.adamw import AdamWState, adamw_init, adamw_update
from repro.optim.grad import clip_by_global_norm
from repro.optim.schedule import warmup_cosine
from repro.runtime import mesh_util

PyTree = Any


class StepBundle(NamedTuple):
    fn: Callable                      # the pure step function
    in_shardings: Tuple               # matching fn's positional args
    out_shardings: Any
    abstract_inputs: Tuple            # ShapeDtypeStructs for lower()
    meta: Dict[str, Any]


# ---------------------------------------------------------------------------
# shared plumbing
# ---------------------------------------------------------------------------

_ABS_CACHE: Dict[Tuple[str, str], Tuple[PyTree, PyTree]] = {}


def abstract_params(cfg: ModelConfig, param_dtype=jnp.float32) -> Tuple[PyTree, PyTree]:
    """(ShapeDtypeStruct params tree, logical axes tree) — no allocation.

    ``init_params`` returns (params, axes) where axes leaves are string
    tuples eval_shape cannot trace through, so axes are captured side-band.
    """
    key = (cfg.to_json(), str(param_dtype))
    if key not in _ABS_CACHE:
        holder: Dict[str, PyTree] = {}

        def build():
            p, a = model_lib.init_params(cfg, jax.random.PRNGKey(0),
                                         param_dtype=param_dtype)
            holder["axes"] = a
            return p

        params_shapes = jax.eval_shape(build)
        _ABS_CACHE[key] = (params_shapes, holder["axes"])
    return _ABS_CACHE[key]


def batch_abstract(cfg: ModelConfig, shape: ShapeConfig,
                   batch_override: Optional[int] = None) -> Dict[str, jax.ShapeDtypeStruct]:
    return {k: jax.ShapeDtypeStruct(s, d)
            for k, (s, d) in batch_shapes(cfg, shape, batch_override).items()}


def sharding_ctx(cfg: ModelConfig, run: RunConfig, mesh: Mesh):
    rules = mesh_util.make_rules(run.sharding, mesh)
    # training keeps f32 master weights; serving deploys bf16 (half the
    # HBM/ICI for weight reads — §Perf serving-feasibility iteration)
    pdtype = jnp.float32 if run.shape.kind == "train" else jnp.bfloat16
    params_shapes, axes = abstract_params(cfg, param_dtype=pdtype)
    pspecs = mesh_util.param_specs(axes, params_shapes, rules, mesh)
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                          is_leaf=lambda x: isinstance(x, P))
    return rules, params_shapes, axes, pspecs, pshard


def _bundle_fabric(cfg: ModelConfig, mesh: Mesh, rules, *, kind: str,
                   weight_reuse: int = 1,
                   log_choice: Optional[list] = None
                   ) -> Tuple[Fabric, Optional[Callable]]:
    """One Fabric per step bundle — the bundle's invocation + telemetry
    surface (``bundle.meta["fabric"]``; Trainer/Server delegate to its
    ``metrics()``). Registers the MoE jam transport when the config and
    mesh call for it; otherwise the fabric carries telemetry only and the
    single-device oracle path runs."""
    fabric = Fabric(mesh, dp_axes=rules.dp_axes, tp_axis=rules.tp_axis,
                    name=f"steps.{kind}")
    if cfg.moe is None or mesh.shape.get(rules.tp_axis, 1) <= 1:
        return fabric, None   # single tensor shard: oracle path
    transport = fabric.moe_transport(mode=cfg.moe.transport,
                                     weight_reuse=weight_reuse,
                                     log_choice=log_choice)
    return fabric, transport


def opt_shardings(pshard: PyTree, mesh: Mesh) -> AdamWState:
    """Optimizer state shardings mirror the params (ZeRO-1 for free)."""
    rep = NamedSharding(mesh, P())
    return AdamWState(step=rep,
                      m=jax.tree.map(lambda s: s, pshard),
                      v=jax.tree.map(lambda s: s, pshard))


def abstract_opt_state(params_shapes: PyTree) -> AdamWState:
    f32 = lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32)
    return AdamWState(step=jax.ShapeDtypeStruct((), jnp.int32),
                      m=jax.tree.map(f32, params_shapes),
                      v=jax.tree.map(f32, params_shapes))



def act_constrain(rules, mesh: Mesh, dp_ok: bool):
    """Batch-dim sharding constraint for (B, S, d) activations.

    Pins the batch axis to the dp mesh axes through the whole network —
    without it GSPMD may replicate the batch once params are FSDP-sharded
    (16x redundant compute; EXPERIMENTS.md §Perf iteration 1)."""
    dp = rules.dp_axes if len(rules.dp_axes) > 1 else (
        rules.dp_axes[0] if rules.dp_axes else None)
    if not dp_ok:
        dp = None
    sh3 = NamedSharding(mesh, P(dp, None, None))

    def constrain(x):
        if getattr(x, "ndim", 0) == 3:
            return jax.lax.with_sharding_constraint(x, sh3)
        return x

    return constrain


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, run: RunConfig, mesh: Mesh,
                    batch_override: Optional[int] = None) -> StepBundle:
    rules, params_shapes, axes, pspecs, pshard = sharding_ctx(cfg, run, mesh)
    ocfg = run.optimizer

    accum = max(1, ocfg.accum_steps)
    # auto-mode transport decisions land here at trace time (surfaced via
    # bundle.meta["transport_log"] -> Trainer logs). weight_reuse stays 1:
    # the transport is traced once inside the accum lax.scan body, so the
    # gather executes per microbatch — pricing amortization the runtime
    # doesn't realize would flip auto mode to 'injected' too early. (Eager
    # callers that reuse weights across calls get the gather cache and may
    # pass weight_reuse themselves.)
    transport_log: list = []
    fabric, transport = _bundle_fabric(cfg, mesh, rules, kind="train",
                                       log_choice=transport_log)

    def grads_of(params, batch):
        def loss_of(p):
            return model_lib.loss_fn(cfg, p, batch, moe_transport=transport,
                                     constrain=constrain)
        return jax.value_and_grad(loss_of, has_aux=True)(params)

    def train_step(params, opt: AdamWState, batch):
        if accum == 1:
            (loss, metrics), grads = grads_of(params, batch)
        else:
            # gradient accumulation: scan over microbatches so only one
            # microbatch of activations is ever live (HBM feasibility at
            # global_batch=256) while grads accumulate in f32
            def split_micro(key, t):
                if key == "mrope_positions":         # (3, B, S): batch dim 1
                    return jnp.moveaxis(
                        t.reshape(t.shape[0], accum, t.shape[1] // accum,
                                  *t.shape[2:]), 1, 0)
                return t.reshape(accum, t.shape[0] // accum, *t.shape[1:])

            micro = {k: split_micro(k, v) for k, v in batch.items()}

            def step_fn(carry, mb):
                gsum, loss_sum, msum = carry
                (loss, metrics), g = grads_of(params, mb)
                gsum = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), gsum, g)
                msum = jax.tree.map(lambda a, b: a + b, msum, metrics)
                return (gsum, loss_sum + loss, msum), None

            gzero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            mzero = {"ce": jnp.float32(0), "aux": jnp.float32(0),
                     "tokens": jnp.float32(0)}
            (gsum, loss_sum, msum), _ = jax.lax.scan(
                step_fn, (gzero, jnp.float32(0), mzero), micro)
            grads = jax.tree.map(lambda g: g / accum, gsum)
            loss = loss_sum / accum
            metrics = dict(msum, ce=msum["ce"] / accum, aux=msum["aux"] / accum)
        grads, gnorm = clip_by_global_norm(grads, ocfg.grad_clip)
        lr = warmup_cosine(opt.step, ocfg)
        new_params, new_opt = adamw_update(grads, opt, params, lr, ocfg)
        metrics = dict(metrics, loss=loss, grad_norm=gnorm, lr=lr)
        return new_params, new_opt, metrics

    batch_abs = batch_abstract(cfg, run.shape, batch_override)
    dp_ok = batch_abs["tokens"].shape[0] % mesh_util.dp_extent(rules, mesh) == 0
    constrain = act_constrain(rules, mesh, dp_ok)
    bspecs = mesh_util.token_batch_specs(
        rules, has_features="features" in batch_abs,
        has_mrope="mrope_positions" in batch_abs, dp_ok=dp_ok)
    bshard = {k: NamedSharding(mesh, bspecs[k]) for k in batch_abs}
    oshard = opt_shardings(pshard, mesh)
    rep = NamedSharding(mesh, P())
    metric_keys = ("ce", "aux", "tokens", "loss", "grad_norm", "lr")

    return StepBundle(
        fn=train_step,
        in_shardings=(pshard, oshard, bshard),
        out_shardings=(pshard, oshard, {k: rep for k in metric_keys}),
        abstract_inputs=(params_shapes, abstract_opt_state(params_shapes),
                         batch_abs),
        meta=dict(rules=rules, pspecs=pspecs, axes=axes, kind="train",
                  batch=batch_abs, transport_log=transport_log,
                  fabric=fabric),
    )


# ---------------------------------------------------------------------------
# prefill step (inference: full-sequence forward, cache filled)
# ---------------------------------------------------------------------------

def make_prefill_step(cfg: ModelConfig, run: RunConfig, mesh: Mesh,
                      batch_override: Optional[int] = None) -> StepBundle:
    rules, params_shapes, axes, pspecs, pshard = sharding_ctx(cfg, run, mesh)
    transport_log: list = []
    fabric, transport = _bundle_fabric(cfg, mesh, rules, kind="prefill",
                                       log_choice=transport_log)
    shape = run.shape
    b = batch_override or shape.global_batch
    seq_sharded = rules.seq_axis is not None

    def prefill_step(params, batch):
        cache = (None if cfg.is_encoder else
                 model_lib.init_cache(cfg, b, shape.seq_len))
        logits, new_cache, _ = model_lib.forward(
            cfg, params, batch["tokens"],
            frontend_feats=batch.get("features"),
            mrope_positions=batch.get("mrope_positions"),
            cache=cache, moe_transport=transport, constrain=constrain)
        # serving returns only the last-position logits (next-token) + cache
        last = logits[:, -1, :]
        if cfg.is_encoder:
            return logits, None
        return last, new_cache

    batch_abs = batch_abstract(cfg, shape, batch_override)
    batch_abs.pop("labels")
    dp_ok = b % mesh_util.dp_extent(rules, mesh) == 0
    constrain = act_constrain(rules, mesh, dp_ok)
    bspecs = mesh_util.token_batch_specs(
        rules, has_features="features" in batch_abs,
        has_mrope="mrope_positions" in batch_abs, seq_sharded=seq_sharded,
        dp_ok=dp_ok)
    bspecs.pop("labels", None)
    bshard = {k: NamedSharding(mesh, bspecs[k]) for k in batch_abs}

    cache_shapes = (None if cfg.is_encoder else jax.eval_shape(
        lambda: model_lib.init_cache(cfg, b, shape.seq_len)))
    cache_shard = (None if cache_shapes is None else jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        mesh_util.cache_spec_tree(cache_shapes, rules, mesh, batch=b,
                                  seq_sharded=seq_sharded),
        is_leaf=lambda x: isinstance(x, P)))
    dp = rules.dp_axes if len(rules.dp_axes) > 1 else (
        rules.dp_axes[0] if rules.dp_axes else None)
    if not dp_ok:
        dp = None
    vocab_tp = mesh_util.tp_vocab_axis(rules, mesh, cfg.vocab_size)
    logit_shard = NamedSharding(
        mesh, P(dp, vocab_tp) if not cfg.is_encoder
        else P(dp, None, vocab_tp))

    return StepBundle(
        fn=prefill_step,
        in_shardings=(pshard, bshard),
        out_shardings=(logit_shard, cache_shard),
        abstract_inputs=(params_shapes, batch_abs),
        meta=dict(rules=rules, pspecs=pspecs, axes=axes, kind="prefill",
                  batch=batch_abs, transport_log=transport_log,
                  fabric=fabric),
    )


# ---------------------------------------------------------------------------
# decode step (inference: one token, KV cache of seq_len)
# ---------------------------------------------------------------------------

def make_serve_step(cfg: ModelConfig, run: RunConfig, mesh: Mesh,
                    batch_override: Optional[int] = None) -> StepBundle:
    assert not cfg.is_encoder, "encoder-only arch has no decode step"
    rules, params_shapes, axes, pspecs, pshard = sharding_ctx(cfg, run, mesh)
    transport_log: list = []
    # weight_reuse stays 1: the decode step is compiled once and every
    # executed tick re-runs the gather inside it, so auto mode must price
    # the full per-call cost (see make_train_step)
    fabric, transport = _bundle_fabric(cfg, mesh, rules, kind="decode",
                                       log_choice=transport_log)
    shape = run.shape
    b = batch_override or shape.global_batch
    constrain = act_constrain(
        rules, mesh, b % mesh_util.dp_extent(rules, mesh) == 0)
    # decode/long cells shard the KV-cache sequence dim over the tensor axis
    # (flash-decode style): the cache dominates memory at 32k-500k.
    seq_sharded = rules.seq_axis is not None

    def serve_step(params, cache, token, mrope_positions=None):
        logits, new_cache = model_lib.decode_step(
            cfg, params, cache, token, moe_transport=transport,
            mrope_positions=mrope_positions, constrain=constrain)
        next_tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return next_tok[:, None], new_cache

    cache_shapes = jax.eval_shape(
        lambda: model_lib.init_cache(cfg, b, shape.seq_len))
    cache_shard = jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        mesh_util.cache_spec_tree(cache_shapes, rules, mesh, batch=b,
                                  seq_sharded=seq_sharded),
        is_leaf=lambda x: isinstance(x, P))
    dp = rules.dp_axes if len(rules.dp_axes) > 1 else (
        rules.dp_axes[0] if rules.dp_axes else None)
    if b % mesh_util.dp_extent(rules, mesh) != 0:
        dp = None
    tok_shard = NamedSharding(mesh, P(dp, None))
    tok_abs = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    abstract = [params_shapes, cache_shapes, tok_abs]
    in_sh = [pshard, cache_shard, tok_shard]
    if cfg.attention is not None and cfg.attention.mrope:
        abstract.append(jax.ShapeDtypeStruct((3, b, 1), jnp.int32))
        in_sh.append(NamedSharding(mesh, P(None, dp, None)))

    return StepBundle(
        fn=serve_step,
        in_shardings=tuple(in_sh),
        out_shardings=(tok_shard, cache_shard),
        abstract_inputs=tuple(abstract),
        meta=dict(rules=rules, pspecs=pspecs, axes=axes, kind="decode",
                  cache=cache_shapes, transport_log=transport_log,
                  fabric=fabric),
    )


# ---------------------------------------------------------------------------
# paged serve step (serving: block-pool cache, decode + chunked prefill in
# one compiled shape — no per-bucket prefill jits)
# ---------------------------------------------------------------------------

def make_paged_serve_step(cfg: ModelConfig, run: RunConfig, mesh: Mesh, *,
                          slots: int, chunk: int, num_blocks: int,
                          block_size: int, max_blocks_per_seq: int,
                          kernel: str = "auto",
                          emit: str = "last") -> StepBundle:
    """One step through the paged pool for ``slots`` request rows.

    fn(params, cache, tokens (slots, chunk), block_tables
    (slots, max_blocks_per_seq), starts (slots,), n_valid (slots,)) ->
    (next_token (slots,), new_cache). ``next_token`` is the greedy argmax at
    each row's last *valid* column; rows mid-prefill get a token the
    scheduler ignores. The same compiled fn serves decode rows (n_valid=1),
    chunked-prefill rows (n_valid up to chunk), and idle rows (n_valid=0).

    ``emit="all"`` is the speculative-decoding *verify* wiring
    (fabric.graph): the step instead returns the greedy argmax at **every**
    chunk column, shape ``(slots, chunk)`` — column ``i`` is the target's
    next-token choice given the row's resident prefix plus the fed tokens
    through column ``i``. Verifying k drafted tokens is then one call of
    the existing chunked-prefill shape (``n_valid = k + 1``): compare
    column ``i`` against draft token ``i + 1``. The per-position math is
    identical to ``emit="last"`` (same forward, same kernel, same cache
    writes) — only the argmax reduction widens — which is what makes
    speculation bitwise output-neutral against target-only decode.

    ``emit="logits"`` returns the float32 logits at every chunk column,
    shape ``(slots, chunk, V)`` — what a comparison with a reference
    forward reads (``repro.engine.teacher_forcing``).

    ``kernel`` selects the paged-attention path (``"pallas"``: the
    stash-resident block-table kernel; ``"ref"``: gather-then-dense;
    ``"auto"``: pallas). The resolved
    choice lands in ``meta["paged_kernel"]``. On multi-device meshes the
    pallas path lowers through ``make_sharded_paged_attention`` — kv heads
    shard over the tensor axis (matching ``paged_cache_spec_tree``'s pool
    sharding), request rows over the data axes, scheduler arrays stay
    replicated at the step boundary and are sliced per dp shard inside the
    shard_map (docs/serving.md#the-paged-attention-kernel).

    ``meta["kv_pool_in_place_layers"]`` of ``meta["kv_pool_layers"]``
    layers scatter their new k/v straight into the donated pool
    (``model.pool_writes_in_place``); the rest copy their pool slice out
    and back. ``meta["latent_layers"]`` counts the MLA layers, whose pool is
    one latent leaf.

    Where the expert layers hold a share of the experts
    (``MoEConfig.experts_held``, ``meta["moe_counters"]``), ``emit="last"``
    also returns the step's expert counters, int32 (3,), summed over the
    expert layers: the fed tokens' top-k picks, those that go to held
    experts, and the most picks one held expert took in a layer.

    MoE archs on a >1-shard tensor axis serve through the token-mask-aware
    jam transports: the padding-column mask from ``PagedLayout.token_valid``
    threads into ``core.dispatch``'s shard bodies so padding can never
    steal expert capacity from real tokens (docs/fabric.md).
    """
    assert not cfg.is_encoder, "encoder-only arch has no decode step"
    if emit not in ("last", "all", "logits"):
        raise ValueError(
            f"emit must be 'last', 'all' or 'logits', got {emit!r}")
    rules, params_shapes, axes, pspecs, pshard = sharding_ctx(cfg, run, mesh)
    paged_kernel = paged_attention_lib.resolve_kernel(
        kernel, n_devices=mesh.devices.size)
    kernel_fn = paged_kernel
    if paged_kernel == "pallas" and mesh.devices.size > 1:
        # the multi-device lowering: same kernel, shard_map'd through the
        # sharded_call seam; the model layer just sees a callable
        kernel_fn = paged_attention_lib.make_sharded_paged_attention(
            mesh, dp_axes=rules.dp_axes, tp_axis=rules.tp_axis)
    transport_log: list = []
    # weight_reuse stays 1 for the same reason as make_serve_step: the step
    # is compiled once and every executed tick re-runs the traced gather
    fabric, transport = _bundle_fabric(cfg, mesh, rules, kind="paged_decode",
                                       log_choice=transport_log)
    constrain = act_constrain(
        rules, mesh, slots % mesh_util.dp_extent(rules, mesh) == 0)

    # an expert layer that holds a share of the experts reports its
    # counters through forward's aux (blocks._apply_block_paged); the
    # tick's step returns them beside the tokens, fetched with them
    moe_counters = (emit == "last" and cfg.moe is not None
                    and cfg.moe.experts_held is not None)

    def paged_step(params, cache, tokens, block_tables, starts, n_valid):
        layout = PagedLayout(block_tables, starts, n_valid, block_size)
        logits, new_cache, aux = model_lib.forward(
            cfg, params, tokens, cache=cache, paged=layout,
            paged_kernel=kernel_fn,
            moe_transport=transport, constrain=constrain)
        if emit == "logits":
            return logits, new_cache                     # (slots, chunk, V)
        if emit == "all":
            # verify wiring: greedy choice at every fed position
            next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return next_tok, new_cache                        # (slots, chunk)
        with jax.named_scope("head"):
            last = jnp.maximum(n_valid - 1, 0)
            last_logits = jnp.take_along_axis(
                logits, last[:, None, None], axis=1)[:, 0]    # (slots, V)
            next_tok = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
        if moe_counters:
            return next_tok, new_cache, aux.astype(jnp.int32)
        return next_tok, new_cache

    cache_shapes = jax.eval_shape(
        lambda: model_lib.init_paged_cache(cfg, num_blocks, block_size))
    in_place_layers, pool_layers = model_lib.paged_in_place_layers(
        cfg, cache_shapes)
    latent_layers = model_lib.paged_latent_layers(cfg, cache_shapes)
    cache_shard = jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        mesh_util.paged_cache_spec_tree(cache_shapes, rules, mesh),
        is_leaf=lambda x: isinstance(x, P))
    rep = NamedSharding(mesh, P())
    # scheduler-side arrays stay replicated: slots is small and often not
    # divisible by the dp extent; the pool itself carries the memory
    abstract = (params_shapes, cache_shapes,
                jax.ShapeDtypeStruct((slots, chunk), jnp.int32),
                jax.ShapeDtypeStruct((slots, max_blocks_per_seq), jnp.int32),
                jax.ShapeDtypeStruct((slots,), jnp.int32),
                jax.ShapeDtypeStruct((slots,), jnp.int32))
    in_sh = (pshard, cache_shard, rep, rep, rep, rep)

    return StepBundle(
        fn=paged_step,
        in_shardings=in_sh,
        out_shardings=(rep, cache_shard) + ((rep,) if moe_counters else ()),
        abstract_inputs=abstract,
        meta=dict(rules=rules, pspecs=pspecs, axes=axes,
                  kind={"last": "paged_decode", "all": "paged_verify",
                        "logits": "paged_logits"}[emit],
                  cache=cache_shapes, transport_log=transport_log,
                  fabric=fabric, block_size=block_size,
                  num_blocks=num_blocks, chunk=chunk, slots=slots,
                  paged_kernel=paged_kernel, emit=emit,
                  kv_pool_in_place_layers=in_place_layers,
                  kv_pool_layers=pool_layers, latent_layers=latent_layers,
                  moe_counters=moe_counters),
    )


# ---------------------------------------------------------------------------
# recurrent serve step (serving: constant-size conv+state carry, decode +
# chunked prefill in one compiled shape — mamba/xLSTM archs)
# ---------------------------------------------------------------------------

def make_recurrent_serve_step(cfg: ModelConfig, run: RunConfig, mesh: Mesh, *,
                              slots: int, chunk: int, max_len: int
                              ) -> StepBundle:
    """One step through per-slot recurrent state for ``slots`` request rows.

    fn(params, cache, tokens (slots, chunk), starts (slots,), n_valid
    (slots,)) -> (next_token (slots,), new_cache). Same contract as the
    paged step minus block tables: rows carry a valid-prefix token layout
    and every state update at an invalid column is gated off inside the
    recurrence, so each row's scan is bitwise what it would be with its
    tokens alone. The cache is O(slots) regardless of sequence length —
    eviction is a cheap state snapshot, never a recompute.
    """
    assert not cfg.is_encoder, "encoder-only arch has no decode step"
    bts = set(model_lib.flat_block_types(cfg))
    bad = sorted(bts - set(blocks_mod.RECURRENT_BLOCK_TYPES))
    if bad:
        raise ValueError(
            f"recurrent serving supports block types "
            f"{blocks_mod.RECURRENT_BLOCK_TYPES}, got {bad} — these carry "
            "seq-sized KV state; use cache='paged' or 'slots' for this arch")
    rules, params_shapes, axes, pspecs, pshard = sharding_ctx(cfg, run, mesh)
    transport_log: list = []
    fabric, transport = _bundle_fabric(cfg, mesh, rules,
                                       kind="recurrent_decode",
                                       log_choice=transport_log)
    constrain = act_constrain(
        rules, mesh, slots % mesh_util.dp_extent(rules, mesh) == 0)

    def recurrent_step(params, cache, tokens, starts, n_valid):
        layout = RecurrentLayout(starts, n_valid)
        logits, new_cache, _ = model_lib.forward(
            cfg, params, tokens, cache=cache, recurrent=layout,
            moe_transport=transport, constrain=constrain)
        with jax.named_scope("head"):
            last = jnp.maximum(n_valid - 1, 0)
            last_logits = jnp.take_along_axis(
                logits, last[:, None, None], axis=1)[:, 0]    # (slots, V)
            next_tok = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
        return next_tok, new_cache

    cache_shapes = jax.eval_shape(
        lambda: model_lib.init_cache(cfg, slots, max_len))
    cache_shard = jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        mesh_util.cache_spec_tree(cache_shapes, rules, mesh, batch=slots,
                                  seq_sharded=False),
        is_leaf=lambda x: isinstance(x, P))
    rep = NamedSharding(mesh, P())
    abstract = (params_shapes, cache_shapes,
                jax.ShapeDtypeStruct((slots, chunk), jnp.int32),
                jax.ShapeDtypeStruct((slots,), jnp.int32),
                jax.ShapeDtypeStruct((slots,), jnp.int32))
    in_sh = (pshard, cache_shard, rep, rep, rep)

    return StepBundle(
        fn=recurrent_step,
        in_shardings=in_sh,
        out_shardings=(rep, cache_shard),
        abstract_inputs=abstract,
        meta=dict(rules=rules, pspecs=pspecs, axes=axes,
                  kind="recurrent_decode", cache=cache_shapes,
                  transport_log=transport_log, fabric=fabric,
                  chunk=chunk, slots=slots),
    )


def make_step(cfg: ModelConfig, run: RunConfig, mesh: Mesh,
              batch_override: Optional[int] = None) -> StepBundle:
    kind = run.shape.kind
    if kind == "train":
        return make_train_step(cfg, run, mesh, batch_override)
    if kind == "prefill":
        return make_prefill_step(cfg, run, mesh, batch_override)
    if kind == "decode":
        return make_serve_step(cfg, run, mesh, batch_override)
    raise ValueError(kind)
