"""Logical-axis -> mesh-axis sharding resolution (GSPMD rule table).

Every parameter in ``repro.models`` carries a tuple of logical axis names
(``("embed", "heads", "head_dim")`` ...). This module turns those names into
``PartitionSpec``s for a concrete mesh, with divisibility-aware fallback:

  * tensor-parallel axes (vocab / ff / moe_ff / expert / heads / kv_heads)
    map to the ``tp_axis`` ("model");
  * ``embed`` (the d_model dims) maps to the FSDP axes (("pod",) +) ("data",)
    when ``fsdp_params`` — ZeRO-3-style parameter sharding;
  * a mesh axis is used at most once per tensor, and an assignment is dropped
    (replicated) whenever the dim size is not divisible by the axis size —
    e.g. gemma3's 8 q-heads cannot split 16-way, so its attention weights fall
    back to FSDP-only sharding instead of failing to lower.

The same rule table shards activations/batches (batch -> dp axes, optional
sequence-parallel axis for long-context cells) and optimizer state (which
follows its parameter: ZeRO-1 for free).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ShardingConfig

PyTree = Any

# Logical axes that never shard (scan-stacked layers, tiny dims).
_NEVER = {"layer", "head_dim", "state", None}


@dataclasses.dataclass(frozen=True)
class Rules:
    """Resolved rule table: logical axis -> candidate mesh-axis assignments.

    Each candidate is a tuple of mesh axes (a PartitionSpec entry); the first
    candidate whose axes are all unused on this tensor and whose product
    divides the dim size wins.
    """

    table: Dict[str, Tuple[Tuple[str, ...], ...]]
    dp_axes: Tuple[str, ...]
    tp_axis: str
    seq_axis: Optional[str] = None

    def candidates(self, logical: Optional[str]) -> Tuple[Tuple[str, ...], ...]:
        if logical in _NEVER:
            return ()
        return self.table.get(logical, ())


def make_rules(sharding: ShardingConfig, mesh: Mesh) -> Rules:
    dp = tuple(a for a in sharding.dp_axes if a in mesh.axis_names)
    tp = sharding.tp_axis if sharding.tp_axis in mesh.axis_names else None
    tp_c: Tuple[Tuple[str, ...], ...] = ((tp,),) if tp else ()
    fsdp_c: Tuple[Tuple[str, ...], ...] = ((dp,) if (dp and sharding.fsdp_params) else ())
    table: Dict[str, Tuple[Tuple[str, ...], ...]] = {
        # tensor-parallel dims: tp first, FSDP fallback
        "vocab": tp_c + fsdp_c,
        "ff": tp_c + fsdp_c,
        "moe_ff": tp_c,
        "expert": tp_c,            # EP: experts live on the model axis
        "heads": tp_c,
        "kv_heads": tp_c,
        "kv_lora": (),
        # d_model dims: FSDP
        "embed": fsdp_c,
    }
    return Rules(table=table, dp_axes=dp, tp_axis=sharding.tp_axis,
                 seq_axis=sharding.seq_axis if sharding.seq_axis in mesh.axis_names else None)


def _axis_sizes(mesh: Mesh) -> Dict[str, int]:
    # mesh.shape is an axis-name->size mapping on both Mesh and AbstractMesh
    return dict(mesh.shape)


def spec_for(logical_axes: Sequence[Optional[str]], shape: Sequence[int],
             rules: Rules, mesh: Mesh) -> P:
    """Resolve one tensor's PartitionSpec (divisibility- and conflict-aware)."""
    sizes = _axis_sizes(mesh)
    used: set = set()
    entries = []
    for name, dim in zip(logical_axes, shape):
        chosen: Optional[Tuple[str, ...]] = None
        for cand in rules.candidates(name):
            prod = 1
            for a in cand:
                prod *= sizes[a]
            if any(a in used for a in cand) or prod == 0 or dim % prod != 0:
                continue
            chosen = cand
            break
        if chosen is None:
            entries.append(None)
        else:
            used.update(chosen)
            entries.append(chosen if len(chosen) > 1 else chosen[0])
    # trailing Nones can be dropped but keeping them is harmless/explicit
    return P(*entries)


def param_specs(axes_tree: PyTree, shapes_tree: PyTree, rules: Rules,
                mesh: Mesh) -> PyTree:
    """PartitionSpec tree matching a params tree.

    ``axes_tree`` leaves are logical-axis tuples; ``shapes_tree`` leaves are
    array-likes with ``.shape`` (ShapeDtypeStruct is fine — no allocation).
    """
    is_axes = lambda x: isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x)
    return jax.tree.map(
        lambda a, s: spec_for(a, s.shape, rules, mesh),
        axes_tree, shapes_tree, is_leaf=is_axes)


def param_shardings(axes_tree: PyTree, shapes_tree: PyTree, rules: Rules,
                    mesh: Mesh) -> PyTree:
    return jax.tree.map(lambda spec: NamedSharding(mesh, spec),
                        param_specs(axes_tree, shapes_tree, rules, mesh),
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Activations / batch
# ---------------------------------------------------------------------------

def batch_spec(rules: Rules, *, seq_sharded: bool = False,
               dp_ok: bool = True) -> P:
    """(batch, seq, ...) spec: batch over dp axes, optionally seq over seq_axis.

    ``dp_ok=False`` drops the batch assignment (global batch not divisible by
    the dp extent — e.g. long_500k's batch of 1)."""
    dp = rules.dp_axes if len(rules.dp_axes) > 1 else (
        rules.dp_axes[0] if rules.dp_axes else None)
    if not dp_ok:
        dp = None
    seq = rules.seq_axis if seq_sharded else None
    return P(dp, seq)


def dp_extent(rules: Rules, mesh: Mesh) -> int:
    sizes = _axis_sizes(mesh)
    prod = 1
    for a in rules.dp_axes:
        prod *= sizes[a]
    return prod


def tp_vocab_axis(rules: Rules, mesh: Mesh, vocab: int) -> Optional[str]:
    """The tp axis for a logits dim, or None when vocab doesn't divide."""
    sizes = _axis_sizes(mesh)
    tp = sizes.get(rules.tp_axis, 1)
    return rules.tp_axis if (tp > 1 and vocab % tp == 0) else None


def token_batch_specs(rules: Rules, has_features: bool = False,
                      has_mrope: bool = False,
                      seq_sharded: bool = False,
                      dp_ok: bool = True) -> Dict[str, P]:
    """Specs for a training/serving batch dict (tokens/labels/features/...)."""
    b = batch_spec(rules, seq_sharded=seq_sharded, dp_ok=dp_ok)
    out = {"tokens": b, "labels": b}
    if has_features:
        out["features"] = P(b[0], b[1] if len(b) > 1 else None, None)
    if has_mrope:
        out["mrope_positions"] = P(None, b[0], b[1] if len(b) > 1 else None)
    return out


def paged_cache_spec_tree(cache_shapes: PyTree, rules: Rules,
                          mesh: Mesh) -> PyTree:
    """Paged-pool specs: kv-heads over tp when divisible, else replicated.

    Pool leaves are (num_blocks, block_size, K, D), optionally with a
    leading layer-stack dim — K is always dim -2. There is no batch dim to
    put on the dp axes (the pool is shared by every request), so head
    sharding is the only axis: decode attention then stays collective-free
    per step, exactly like the contiguous cache's kv-head sharding. MLA's
    ``latent`` leaves (num_blocks, block_size, lanes) have no head axis
    and stay replicated.
    """
    sizes = _axis_sizes(mesh)
    tp = rules.tp_axis

    def one(path, x) -> P:
        shape = x.shape
        entries: list = [None] * len(shape)
        latent = bool(path) and getattr(path[-1], "key", None) == "latent"
        if (len(shape) >= 4 and not latent and tp in sizes and sizes[tp] > 1
                and shape[-2] % sizes[tp] == 0):
            entries[-2] = tp
        return P(*entries)

    return jax.tree_util.tree_map_with_path(one, cache_shapes)


def cache_spec_tree(cache_shapes: PyTree, rules: Rules, mesh: Mesh,
                    *, batch: int, seq_sharded: bool = False) -> PyTree:
    """KV-cache specs: batch over dp, kv-heads over tp, seq as fallback.

    Cache leaves may carry a leading layer-stack dim (scan groups broadcast
    to ``(repeats, ...)``), so the batch dim is located structurally: the
    first dim equal to ``batch``. Layout after batch: k/v (T, K, D); MLA
    (T, r); SSM (W|inner, ...). Preference order on the tensor axis:
      1. kv-heads (dim batch+2 of 4 trailing dims) — head-sharded decode
         attention is entirely local, no per-step cache collectives;
      2. the dim right after batch (seq for KV, inner for SSM state) when
         ``seq_sharded`` — the fallback for small-kv archs and long context.
    """
    sizes = _axis_sizes(mesh)
    dp = rules.dp_axes if len(rules.dp_axes) > 1 else (
        rules.dp_axes[0] if rules.dp_axes else None)
    dp_prod = 1
    for a in rules.dp_axes:
        dp_prod *= sizes[a]

    def one(x) -> P:
        shape = x.shape
        if not shape:
            return P()
        try:
            ib = list(shape).index(batch)
        except ValueError:
            return P(*([None] * len(shape)))
        entries: list = [None] * len(shape)
        used: set = set()
        if batch % max(1, dp_prod) == 0 and dp_prod > 1:
            entries[ib] = dp
            used.update(rules.dp_axes)
        trailing = len(shape) - ib - 1
        tp = rules.tp_axis
        if (trailing == 3 and tp in sizes and tp not in used
                and shape[ib + 2] % sizes[tp] == 0):
            entries[ib + 2] = tp               # kv-heads
            used.add(tp)
        if (seq_sharded and rules.seq_axis and rules.seq_axis not in used
                and trailing >= 1
                and shape[ib + 1] % sizes[rules.seq_axis] == 0):
            entries[ib + 1] = rules.seq_axis   # seq (KV) / inner (SSM)
            used.add(rules.seq_axis)
        return P(*entries)

    return jax.tree.map(one, cache_shapes)
