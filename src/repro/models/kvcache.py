"""KV-cache structures for decode. Registered as pytrees so they flow through jit.

Three layouts:
  * ``KVCache``      — standard GQA: k/v (B, S_max, K, D), contiguous per row.
  * ``MLACache``     — deepseek MLA: compressed c_kv (B, S_max, r) + shared rope
    key (B, S_max, rope_dim); ~(2*K*D)/(r+rope) smaller than materialized k/v.
  * ``PagedKVCache`` — serving: one shared block pool (N_blocks, block_size,
    K, D) per layer; requests own blocks through a per-request block table
    so HBM is allocated at actual-sequence-length granularity instead of
    ``slots * max_len`` (the receiver-resident-state pool of docs/serving.md).

Sliding-window layers may allocate ``S_max = window`` and write via ring
indexing (``ring=True``) — the beyond-paper memory optimization for long
contexts (EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import dataclasses
import json
import struct
from typing import (Any, Callable, Dict, NamedTuple, Optional, Protocol,
                    Tuple, runtime_checkable)

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    k: jax.Array                     # (B, S_max, K, D)
    v: jax.Array                     # (B, S_max, K, D)
    length: jax.Array                # () int32 — tokens already in cache
    ring: bool = dataclasses.field(default=False, metadata=dict(static=True))

    @property
    def max_len(self) -> int:
        return self.k.shape[1]

    @staticmethod
    def init(batch: int, max_len: int, kv_heads: int, head_dim: int,
             dtype=jnp.bfloat16, ring: bool = False) -> "KVCache":
        shape = (batch, max_len, kv_heads, head_dim)
        return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                       jnp.zeros((), jnp.int32), ring)

    def append(self, k_new: jax.Array, v_new: jax.Array) -> "KVCache":
        """Append S_new tokens (B, S_new, K, D) at position ``length``.

        Ring caches with a multi-token append must NOT use a single
        ``dynamic_update_slice``: DUS clamps the start index so the slice
        stays in bounds instead of wrapping, which silently shifts every
        token written across the wrap boundary. Those appends scatter to
        explicit ``(length + i) % max_len`` rows instead; a single-token
        ring append can never cross the boundary and keeps the DUS fast
        path.
        """
        s_new = k_new.shape[1]
        new_len = self.length + s_new
        if self.ring and s_new > 1:
            if s_new >= self.max_len:
                # only the last max_len tokens survive a full wrap — drop
                # the overwritten prefix so scatter rows are unique
                k_new = k_new[:, -self.max_len:]
                v_new = v_new[:, -self.max_len:]
                s_new = self.max_len
            # surviving tokens occupy absolute positions [new_len - s_new,
            # new_len); map each to its ring row
            rows = (new_len - s_new
                    + jnp.arange(s_new, dtype=jnp.int32)) % self.max_len
            k = self.k.at[:, rows].set(k_new.astype(self.k.dtype))
            v = self.v.at[:, rows].set(v_new.astype(self.v.dtype))
            return KVCache(k, v, new_len, self.ring)
        pos = self.length % self.max_len if self.ring else self.length
        k = jax.lax.dynamic_update_slice(self.k, k_new.astype(self.k.dtype), (0, pos, 0, 0))
        v = jax.lax.dynamic_update_slice(self.v, v_new.astype(self.v.dtype), (0, pos, 0, 0))
        return KVCache(k, v, new_len, self.ring)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class MLACache:
    c_kv: jax.Array                  # (B, S_max, r)
    k_rope: jax.Array                # (B, S_max, rope_dim)
    length: jax.Array                # () int32

    @property
    def max_len(self) -> int:
        return self.c_kv.shape[1]

    @staticmethod
    def init(batch: int, max_len: int, kv_lora_rank: int, rope_dim: int,
             dtype=jnp.bfloat16) -> "MLACache":
        return MLACache(
            jnp.zeros((batch, max_len, kv_lora_rank), dtype),
            jnp.zeros((batch, max_len, rope_dim), dtype),
            jnp.zeros((), jnp.int32),
        )

    def append(self, c_new: jax.Array, kr_new: jax.Array) -> "MLACache":
        c = jax.lax.dynamic_update_slice(self.c_kv, c_new.astype(self.c_kv.dtype), (0, self.length, 0))
        kr = jax.lax.dynamic_update_slice(self.k_rope, kr_new.astype(self.k_rope.dtype), (0, self.length, 0))
        return MLACache(c, kr, self.length + c_new.shape[1])


class PagedLayout(NamedTuple):
    """Per-step view of the paged pool, built inside the jitted step.

    block_tables: (B, max_blocks) int32 — pool block ids per request, in
        logical order; -1 marks unallocated slots.
    starts: (B,) int32 — tokens already resident per request (the absolute
        position of this step's first new token).
    n_valid: (B,) int32 — how many of this step's ``chunk`` token columns
        are real for each request (decode rows use 1, prefill rows up to
        ``chunk``, idle rows 0).
    block_size: static python int — tokens per pool block.
    """

    block_tables: jax.Array
    starts: jax.Array
    n_valid: jax.Array
    block_size: int

    def token_positions(self, chunk: int) -> jax.Array:
        return (self.starts[:, None]
                + jnp.arange(chunk, dtype=jnp.int32)[None, :])

    def token_valid(self, chunk: int) -> jax.Array:
        return (jnp.arange(chunk, dtype=jnp.int32)[None, :]
                < self.n_valid[:, None])

    def dest_rows(self, num_blocks: int, chunk: int) -> jax.Array:
        """Flat pool-row index per (request, token column); out of bounds
        (``num_blocks * block_size``) where the column is not valid."""
        bs = self.block_size
        pos = self.token_positions(chunk)                      # (B, C)
        blk_idx = jnp.clip(pos // bs, 0, self.block_tables.shape[1] - 1)
        blk = jnp.take_along_axis(self.block_tables, blk_idx, axis=1)
        rows = blk * bs + pos % bs
        return jnp.where(self.token_valid(chunk) & (blk >= 0), rows,
                         num_blocks * bs)

    def write(self, pool: jax.Array, new: jax.Array) -> jax.Array:
        """Scatter ``new`` (B, C, ...) into ``pool`` (N_blocks, block_size,
        ...) at the tokens' logical positions; invalid columns (beyond
        ``n_valid``) are dropped."""
        rows = self.dest_rows(pool.shape[0], new.shape[1]).reshape(-1)
        tail = pool.shape[2:]
        flat = pool.reshape(-1, *tail).at[rows].set(
            new.reshape(-1, *tail).astype(pool.dtype), mode="drop")
        return flat.reshape(pool.shape)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedKVCache:
    """Block-pool GQA cache: requests gather/scatter through a block table.

    The pool is shared by every request; logical position ``p`` of request
    ``b`` lives at ``(block_tables[b, p // block_size], p % block_size)``.
    All ops are fixed-shape (jit-friendly): invalid writes scatter out of
    bounds and are dropped, invalid reads are masked by the caller.
    """

    k_pool: jax.Array                # (N_blocks, block_size, K, D)
    v_pool: jax.Array
    block_size: int = dataclasses.field(default=16, metadata=dict(static=True))

    @property
    def num_blocks(self) -> int:
        return self.k_pool.shape[0]

    @staticmethod
    def init(num_blocks: int, block_size: int, kv_heads: int, head_dim: int,
             dtype=jnp.bfloat16) -> "PagedKVCache":
        shape = (num_blocks, block_size, kv_heads, head_dim)
        return PagedKVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                            block_size)

    def write(self, k_new: jax.Array, v_new: jax.Array,
              layout: PagedLayout) -> "PagedKVCache":
        """Scatter (B, C, K, D) new tokens into the pool at their logical
        positions; invalid columns (beyond ``n_valid``) are dropped."""
        return PagedKVCache(layout.write(self.k_pool, k_new),
                            layout.write(self.v_pool, v_new),
                            self.block_size)

    def gather(self, block_tables: jax.Array,
               seq_lens: Optional[jax.Array] = None):
        """Materialize each request's logical (T, K, D) view, T = M * bs.

        Unallocated table slots (-1) read block 0 — callers mask positions
        ``>= length`` so the garbage never reaches the softmax unmasked.

        With ``seq_lens`` (per-request resident-token counts, (B,)), also
        returns ``max_resident``: the longest live sequence rounded up to
        ``block_size`` and clamped to T. Eager callers (the kernel oracle,
        tests) use it to bound the view to live tokens instead of always
        ``max_blocks * block_size``; under jit it is a tracer and the full
        fixed-shape view stands.
        """
        bs = self.block_size
        B, M = block_tables.shape
        rows = (jnp.clip(block_tables, 0)[:, :, None] * bs
                + jnp.arange(bs, dtype=jnp.int32)[None, None, :])
        rows = rows.reshape(B, M * bs)
        tail = self.k_pool.shape[2:]
        flat_k = self.k_pool.reshape(-1, *tail)
        flat_v = self.v_pool.reshape(-1, *tail)
        if seq_lens is None:
            return flat_k[rows], flat_v[rows]
        max_resident = jnp.minimum(
            -(-jnp.max(seq_lens.astype(jnp.int32)) // bs) * bs, M * bs)
        return flat_k[rows], flat_v[rows], max_resident


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SSMCache:
    """Recurrent state for mamba / xLSTM decode: O(1) in sequence length."""
    conv: jax.Array                  # (B, conv_width-1, inner) rolling conv inputs
    state: jax.Array                 # (B, ...) recurrent state
    extra: Any                       # e.g. sLSTM normalizer / mLSTM (n, m) terms
    length: jax.Array


class RecurrentLayout(NamedTuple):
    """Per-step serving view for recurrent (SSM/xLSTM) stacks.

    The recurrent counterpart of ``PagedLayout`` minus the block tables:
    state is constant-size per request, so the only per-step facts are
    where each row is in its sequence and how many of the ``chunk`` token
    columns are real.

    starts: (B,) int32 — tokens already absorbed into the state per row.
    n_valid: (B,) int32 — real token columns this step (decode rows 1,
        prefill rows up to ``chunk``, idle rows 0).
    """

    starts: jax.Array
    n_valid: jax.Array

    def token_positions(self, chunk: int) -> jax.Array:
        return (self.starts[:, None]
                + jnp.arange(chunk, dtype=jnp.int32)[None, :])

    def token_valid(self, chunk: int) -> jax.Array:
        return (jnp.arange(chunk, dtype=jnp.int32)[None, :]
                < self.n_valid[:, None])


# ---------------------------------------------------------------------------
# state serialization (the migration seam: ROADMAP item 3)
# ---------------------------------------------------------------------------

_STATE_MAGIC = b"RST1"


def _np_dtype(name: str) -> np.dtype:
    if name == "bfloat16":
        return np.dtype(jnp.bfloat16)
    return np.dtype(name)


def state_to_bytes(tree: Any) -> bytes:
    """Pack a pytree of arrays into one buffer: a JSON header (per-leaf
    dtype + shape, in ``tree_leaves`` order) followed by the raw bytes.

    The tree *structure* does not travel — sender and receiver agree on it
    out of band (same model config), exactly like the GOT layout hash of
    docs/fabric.md; only values cross the wire. bf16 round-trips exactly
    (raw ml_dtypes bytes, no float32 detour)."""
    arrs = [np.asarray(leaf) for leaf in jax.tree_util.tree_leaves(tree)]
    header = json.dumps([{"dtype": a.dtype.name, "shape": list(a.shape)}
                         for a in arrs]).encode("utf-8")
    parts = [_STATE_MAGIC, struct.pack("<I", len(header)), header]
    parts.extend(np.ascontiguousarray(a).tobytes() for a in arrs)
    return b"".join(parts)


def state_from_bytes(buf: bytes, like: Any) -> Any:
    """Inverse of ``state_to_bytes``. ``like`` supplies the tree structure
    (arrays or ShapeDtypeStructs); leaf dtype/shape mismatches between the
    buffer and ``like`` raise rather than silently reinterpreting bytes."""
    if buf[:4] != _STATE_MAGIC:
        raise ValueError("state buffer does not start with the RST1 magic")
    (hlen,) = struct.unpack("<I", buf[4:8])
    header = json.loads(buf[8:8 + hlen].decode("utf-8"))
    like_leaves, treedef = jax.tree_util.tree_flatten(like)
    if len(header) != len(like_leaves):
        raise ValueError(
            f"state buffer holds {len(header)} leaves, template has "
            f"{len(like_leaves)}")
    off = 8 + hlen
    out = []
    for meta, ref in zip(header, like_leaves):
        dtype = _np_dtype(meta["dtype"])
        shape = tuple(meta["shape"])
        if (tuple(ref.shape) != shape
                or np.dtype(ref.dtype).name != dtype.name):
            raise ValueError(
                f"state leaf mismatch: buffer has {meta['dtype']}{shape}, "
                f"template expects "
                f"{np.dtype(ref.dtype).name}{tuple(ref.shape)}")
        n = int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
        arr = np.frombuffer(buf[off:off + n], dtype=dtype).reshape(shape)
        off += n
        out.append(jnp.asarray(arr))
    if off != len(buf):
        raise ValueError(f"state buffer has {len(buf) - off} trailing bytes")
    return jax.tree_util.tree_unflatten(treedef, out)


def ssm_cache_to_bytes(cache: SSMCache) -> bytes:
    """Serialize one ``SSMCache`` (conv + state + extra + length)."""
    return state_to_bytes(cache)


def ssm_cache_from_bytes(buf: bytes, like: SSMCache) -> SSMCache:
    """Rebuild an ``SSMCache`` from ``ssm_cache_to_bytes`` output; ``like``
    provides the structure (an init-shaped cache works)."""
    return state_from_bytes(buf, like)


# ---------------------------------------------------------------------------
# SequenceState — the per-request sequence-state backend protocol
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SequenceCapacity:
    """What a backend's admission-limiting resource looks like.

    ``free_units is None`` means the resource is not consumable (a slot
    row or constant-size state exists per slot regardless of sequence
    length) and admission is gated on free slots alone."""

    kind: str                        # backend name ("paged"/"slots"/...)
    unit: str                        # "blocks" | "slots"
    total_units: Optional[int]
    free_units: Optional[int]


@runtime_checkable
class SequenceState(Protocol):
    """Pluggable per-request sequence-state backend for the Engine.

    The engine owns requests and the tick loop; the backend owns what a
    request's *state* is and what it costs: pool blocks (``PagedKVState``),
    a contiguous cache row (``SlotKVState``), or constant-size recurrent
    state (``RecurrentState``). Entries are duck-typed scheduler records
    (``pos``/``blocks``/``snapshot``/``seq()``); ``cache`` is the live
    device pytree, threaded through because several backends rebuild it.
    """

    kind: str
    supports_preemption: bool

    def init(self, entry: Any, cache: Any, slot: int) -> Any:
        """Prepare ``slot`` for ``entry`` at admission; returns the cache."""

    def append(self, entry: Any, n: int) -> None:
        """Host-side accounting after ``n`` tokens entered the state."""

    def gather(self, entry: Any, cache: Any, slot: int) -> Any:
        """Materialize the request's state as a host pytree."""

    def units_needed(self, entry: Any) -> int:
        """Capacity units required to advance this entry one step."""

    def grow(self, entry: Any, upto_tokens: int) -> bool:
        """Reserve capacity for ``upto_tokens``; False when exhausted."""

    def evict(self, entry: Any, cache: Any, slot: int) -> Any:
        """Release/park the entry's state for requeue; returns the cache."""

    def release(self, entry: Any) -> None:
        """Drop all state owned by a finished entry."""

    def serialize(self, entry: Any, cache: Any, slot: int) -> bytes:
        """The migration seam: the request's state as one buffer."""

    def restore(self, entry: Any, cache: Any, slot: int, buf: bytes) -> Any:
        """Inverse of ``serialize``: write a migrated request's state into
        ``slot``. The buffer is position-independent (logical token order,
        no physical block ids / slot indices), so source and target may
        disagree on pool geometry, block allocation, and slot number — only
        the model config and this backend's *kind* must match. Returns the
        updated cache; the entry must already own whatever capacity the
        resident prefix needs (the engine grows it before restoring)."""

    def capacity(self) -> SequenceCapacity: ...

    def metrics(self) -> Dict[str, Any]: ...

    def validate(self, prompt_len: int, max_new: int,
                 max_len: int) -> Optional[str]:
        """Reject-at-submit check; an error string or None."""


def slot_axis(live_shape: Tuple[int, ...], one_shape: Tuple[int, ...],
              slots: int) -> Optional[int]:
    """Locate the batch (slot) axis of a cache leaf structurally: the first
    axis where the live leaf has ``slots`` extent, the one-row template has
    extent 1, and every leading dim matches. (Same rule as the Engine's
    prefill scatter: positional guesses mistake the layer-stack dim for
    batch.) Returns None for leaves with no per-slot axis (scalars)."""
    if len(live_shape) != len(one_shape):
        return None
    for ax in range(len(live_shape)):
        if (live_shape[ax] == slots and one_shape[ax] == 1
                and live_shape[:ax] == one_shape[:ax]):
            return ax
    return None


def gather_slot_rows(cache: Any, template: Any, slot: int, slots: int) -> Any:
    """Slice one slot's rows out of a batched cache (host numpy pytree).
    Leaves without a slot axis (the shared length scalar) copy through."""
    def take(live, one):
        ax = slot_axis(tuple(live.shape), tuple(np.shape(one)), slots)
        if ax is None:
            return np.asarray(live)
        return np.asarray(jax.lax.dynamic_slice_in_dim(live, slot, 1, axis=ax))
    return jax.tree.map(take, cache, template)


def scatter_slot_rows(cache: Any, row: Any, slot: int, slots: int) -> Any:
    """Write one-row state back into ``slot`` of a batched cache. Leaves
    without a slot axis are left untouched."""
    def put(live, one):
        ax = slot_axis(tuple(live.shape), tuple(np.shape(one)), slots)
        if ax is None:
            return live
        start = [0] * live.ndim
        start[ax] = slot
        return jax.lax.dynamic_update_slice(
            live, jnp.asarray(one).astype(live.dtype), tuple(start))
    return jax.tree.map(put, cache, row)


class RecurrentState:
    """``SequenceState`` over constant-size recurrent state (SSM/xLSTM).

    A request's entire sequence state is its ``SSMCache`` rows — O(1) in
    sequence length — so there is no consumable pool: ``grow`` always
    succeeds and admission is gated on free slots alone. Eviction is a
    cheap host snapshot of the slot's rows (``entry.snapshot``); on
    re-admission the snapshot is scattered back and decoding resumes where
    it stopped — never a recompute, which is what makes preemption (and
    ROADMAP item 3's migration) nearly free for these model families.

    ``template_fn`` returns a one-row init cache (NOT zeros: mLSTM carries
    ``m = -inf``, sLSTM ``n = 1``); it also clears a freed slot's stale
    state before a fresh request runs, since recurrent updates would
    otherwise integrate the previous occupant's state.
    """

    kind = "recurrent"
    supports_preemption = True

    def __init__(self, slots: int, template_fn: Callable[[], Any],
                 place: Optional[Callable[[Any], Any]] = None):
        self.slots = slots
        self._template_fn = template_fn
        self._template: Any = None
        self._place = place if place is not None else (lambda t: t)
        self.snapshots_taken = 0
        self.snapshots_restored = 0

    @property
    def template(self) -> Any:
        if self._template is None:
            self._template = jax.tree.map(np.asarray, self._template_fn())
        return self._template

    def state_bytes_per_slot(self) -> int:
        return sum(leaf.nbytes
                   for leaf in jax.tree_util.tree_leaves(self.template)
                   if getattr(leaf, "ndim", 0) > 0)

    def init(self, entry: Any, cache: Any, slot: int) -> Any:
        row = getattr(entry, "snapshot", None)
        restored = row is not None
        if row is None:
            row = self.template
        cache = scatter_slot_rows(cache, row, slot, self.slots)
        if restored:
            entry.snapshot = None
            self.snapshots_restored += 1
        return self._place(cache)

    def append(self, entry: Any, n: int) -> None:
        return None

    def gather(self, entry: Any, cache: Any, slot: int) -> Any:
        return gather_slot_rows(cache, self.template, slot, self.slots)

    def units_needed(self, entry: Any) -> int:
        return 0

    def grow(self, entry: Any, upto_tokens: int) -> bool:
        return True

    def evict(self, entry: Any, cache: Any, slot: int) -> Any:
        # snapshot covers seq[:entry.pos]; pos is deliberately kept so
        # re-admission resumes (feed the next unseen token) instead of
        # re-prefilling — the opposite of the paged recompute path
        entry.snapshot = self.gather(entry, cache, slot)
        self.snapshots_taken += 1
        return cache

    def release(self, entry: Any) -> None:
        if getattr(entry, "snapshot", None) is not None:
            entry.snapshot = None

    def serialize(self, entry: Any, cache: Any, slot: int) -> bytes:
        return state_to_bytes(self.gather(entry, cache, slot))

    def restore(self, entry: Any, cache: Any, slot: int, buf: bytes) -> Any:
        """Scatter a migrated request's state rows into ``slot`` — the
        byte-level twin of the snapshot-resume path (``init`` with
        ``entry.snapshot``), so a migrated request resumes exactly like a
        requeued one: state absorbed through ``entry.pos``, never a
        recompute. Constant-size state is what makes recurrent migration
        nearly free (a few KB regardless of sequence length)."""
        row = state_from_bytes(buf, self.template)
        return self._place(scatter_slot_rows(cache, row, slot, self.slots))

    def capacity(self) -> SequenceCapacity:
        return SequenceCapacity(kind="recurrent", unit="slots",
                                total_units=self.slots, free_units=None)

    def metrics(self) -> Dict[str, Any]:
        return {
            "state_bytes_per_slot": self.state_bytes_per_slot(),
            "snapshots_taken": self.snapshots_taken,
            "snapshots_restored": self.snapshots_restored,
        }

    def validate(self, prompt_len: int, max_new: int,
                 max_len: int) -> Optional[str]:
        return None                  # constant-size state: no length limit
