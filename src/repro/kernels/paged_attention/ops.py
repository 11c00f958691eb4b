"""Jit'd public wrapper + kernel resolution for paged attention.

``paged_attention`` mirrors ``ref.paged_attention_ref``'s signature so the
two are drop-in interchangeable in ``models.attention.gqa_paged_attention``.

``resolve_kernel`` implements the ``kernel="auto"`` policy: the Pallas
kernel, compiled on a TPU and run under the TPU-semantics Pallas
interpreter (``pltpu.InterpretParams``) elsewhere — that is how the CPU
differential tests drive it. The ``ref`` oracle stays explicitly
requestable (``kernel="ref"``).

``make_sharded_paged_attention`` (ISSUE 7) is the kernel's multi-device
lowering: a ``core.transport.sharded_call``-wrapped ``paged_attention``
whose partitioning rule is **kv heads over the tensor axis, request rows
over the data axes** — the same axes the paged pool itself shards on
(``mesh_util.paged_cache_spec_tree``), so each device runs the single-device
kernel against exactly the pool shard and request rows it owns, with zero
per-step collectives. Scheduler arrays (block tables / starts / n_valid)
arrive replicated at the step boundary and are sliced to each dp shard's
rows by the shard_map in_specs.

``modeled_hbm_bytes`` is the per-decode-step KV traffic model behind the
ISSUE's acceptance number (and ``benchmarks/bench_paged_attention.py``):
the ref path materializes a batch-uniform logical view bounded by the
*longest* live sequence (``max_resident``, block-rounded — ``ref.py``'s
eager slice) and reads it twice (gather + score), while the kernel streams
each request's own live blocks into VMEM exactly once — so ref traffic
scales with ``B * max(resident)`` and kernel traffic with ``sum(resident)``.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.experimental.pallas.tpu as pltpu
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.kernels.paged_attention.kernel import paged_attention_pallas
from repro.kernels.paged_attention.ref import paged_attention_ref

KERNEL_KINDS = ("auto", "pallas", "ref")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_kernel(kind: str, n_devices: int = 1) -> str:
    """Resolve ``"auto"`` to the kernel that serves: always ``"pallas"``.

    On a TPU the kernel compiles through Mosaic; elsewhere it runs under
    the TPU-semantics Pallas interpreter (``pltpu.InterpretParams``) —
    semantics-faithful, but Python-interpreted, so CPU deployments that
    care about throughput pass ``--paged-kernel ref`` explicitly
    (docs/serving.md). Device count does not matter: on >1-device meshes
    the kernel lowers through ``make_sharded_paged_attention`` (kv heads
    over tp, request rows over dp).
    """
    if kind not in KERNEL_KINDS:
        raise ValueError(f"kernel must be one of {KERNEL_KINDS}, got {kind!r}")
    del n_devices  # the sharded lowering serves every device count
    return "pallas" if kind == "auto" else kind


def _resolve_interpret(interpret: bool | None) -> object:
    """None => auto: the TPU-semantics interpreter off-TPU, Mosaic on TPU."""
    interp: object = (not _on_tpu()) if interpret is None else interpret
    return pltpu.InterpretParams() if interp else False


@partial(jax.jit, static_argnames=("block_size", "window", "scale",
                                   "interpret"))
def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    block_tables: jax.Array, starts: jax.Array,
                    n_valid: jax.Array, *, block_size: int,
                    window: Optional[int] = None,
                    scale: Optional[float] = None,
                    interpret: bool | None = None) -> jax.Array:
    """(B,C,H,D) x pool -> (B,C,H,D). interpret=None => auto (off-TPU runs
    the TPU-semantics interpreter)."""
    return paged_attention_pallas(
        q, k_pool, v_pool, block_tables, starts, n_valid,
        block_size=block_size, window=window, scale=scale,
        interpret=_resolve_interpret(interpret))


def sharded_paged_specs(mesh: Mesh, *, batch: int, kv_heads: int,
                        dp_axes: Sequence[str] = ("data",),
                        tp_axis: str = "model") -> Tuple[object, Optional[str]]:
    """The kernel's partitioning rule, divisibility-gated like the rest of
    the repo: request rows shard over the dp axes iff ``batch`` divides the
    dp extent (``act_constrain``'s rule), kv heads over ``tp_axis`` iff
    ``kv_heads`` divides it (``paged_cache_spec_tree``'s rule). Returns
    ``(dp_entry, tp_entry)`` PartitionSpec entries (either may be None)."""
    sizes = dict(mesh.shape)
    dp_axes = tuple(a for a in dp_axes if a in sizes)
    dp_prod = 1
    for a in dp_axes:
        dp_prod *= sizes[a]
    dp: object = dp_axes if len(dp_axes) > 1 else (
        dp_axes[0] if dp_axes else None)
    if dp_prod <= 1 or batch % dp_prod != 0:
        dp = None
    tp: Optional[str] = tp_axis
    if sizes.get(tp_axis, 1) <= 1 or kv_heads % sizes[tp_axis] != 0:
        tp = None
    return dp, tp


def make_sharded_paged_attention(mesh: Mesh, *,
                                 dp_axes: Sequence[str] = ("data",),
                                 tp_axis: str = "model",
                                 interpret: bool | None = None) -> Callable:
    """Multi-device ``paged_attention`` through the ``sharded_call`` seam.

    Returns a callable with ``paged_attention_ref``'s signature. The
    shard_map body is the unmodified single-device kernel: q rows and the
    per-request scheduler arrays split over the dp axes, kv heads (and both
    pool leaves) over the tensor axis. q's head layout ``h = k * G + g``
    makes a contiguous H/tp slice exactly the group heads of a contiguous
    K/tp kv-head slice, so head sharding aligns with the pool's kv-head
    sharding and the body needs **no collectives** — each device scores its
    own request rows against its own pool shard, which is the Two-Chains
    locality argument at the kernel layer (run the function where the
    injected state lives; docs/serving.md#the-paged-attention-kernel).

    When a dim does not divide (slots % dp, K % tp) that dim stays
    replicated — same fallback the pool specs use — and the body computes
    redundantly on the affected axis instead of wrongly.
    """
    from repro.core.transport import sharded_call

    def call(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
             block_tables: jax.Array, starts: jax.Array, n_valid: jax.Array,
             *, block_size: int, window: Optional[int] = None,
             scale: Optional[float] = None) -> jax.Array:
        B = q.shape[0]
        K = k_pool.shape[2]
        dp, tp = sharded_paged_specs(mesh, batch=B, kv_heads=K,
                                     dp_axes=dp_axes, tp_axis=tp_axis)
        interp = _resolve_interpret(interpret)

        def body(qs, ks, vs, tb, st, nv):
            return paged_attention_pallas(
                qs, ks, vs, tb, st, nv, block_size=block_size,
                window=window, scale=scale, interpret=interp)

        fn = sharded_call(
            body, mesh,
            in_specs=(P(dp, None, tp, None),          # q: rows x heads
                      P(None, None, tp, None),        # k_pool: kv heads
                      P(None, None, tp, None),        # v_pool
                      P(dp, None),                    # block_tables: rows
                      P(dp,), P(dp,)),                # starts / n_valid
            out_specs=P(dp, None, tp, None),
            label="paged_attention.pallas")
        return fn(q, k_pool, v_pool,
                  block_tables.astype(jnp.int32),
                  starts.astype(jnp.int32), n_valid.astype(jnp.int32))

    return call


def modeled_hbm_bytes(seq_lens: Sequence[int], *, block_size: int,
                      max_blocks: int, kv_heads: int, head_dim: int,
                      dtype_bytes: int = 2, kernel: str = "pallas") -> int:
    """Modeled KV HBM bytes *read* by one attention step (k + v).

    ref:    the gathered logical view is batch-uniform and bounded by the
            **longest** live sequence — ``max_resident`` = block-rounded
            ``max(seq_lens)``, clamped to ``[block_size, max_blocks * bs]``
            (``ref.py``'s eager slice) — and is read twice: once gathering
            it out of the pool, once scoring the materialized copy. Every
            request pays the straggler's length.
    pallas: each request's live blocks are DMA'd pool->VMEM once; dead
            table slots are never addressed — 1 pass over each request's
            own ``ceil(seq_len / bs) * bs`` rows.
    """
    row = kv_heads * head_dim * dtype_bytes * 2          # one k row + v row
    lens = [int(s) for s in seq_lens]
    if kernel == "ref":
        longest = max(lens, default=0)
        t = min(max(-(-longest // block_size), 1) * block_size,
                max_blocks * block_size)
        return 2 * len(lens) * t * row
    live_rows = sum(-(-s // block_size) * block_size for s in lens)
    return live_rows * row
