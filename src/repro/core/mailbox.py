"""Reactive mailboxes (paper §III-A / Fig. 1) — banked frame buffers with
credit flow control, a one-sided put transport, and drain-on-arrival
execution.

Transport layers (lowest first):
  1. ``kernels/mailbox`` — Pallas remote-DMA kernel (send/recv semaphores =
     the signal-word wait; the real TPU path).
  2. ``ring_put`` / ``alltoall_put`` here — ``shard_map`` + ``jax.lax``
     collectives: the portable reference used by tests/benchmarks.
  3. ``post_local`` — loopback for single-device tests.

Flow control mirrors §VI-A2: the receiver has M banks x N frame slots; the
sender holds one credit flag per bank and stops sending to a bank until the
receiver drains it and returns the credit.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.message import FrameSpec


@dataclasses.dataclass(frozen=True)
class MailboxConfig:
    banks: int = 4
    frames_per_bank: int = 16
    spec: FrameSpec = dataclasses.field(default_factory=FrameSpec)

    @property
    def words(self) -> int:
        return self.spec.total_words


def init_mailbox(cfg: MailboxConfig) -> Dict[str, jax.Array]:
    """Pinned-memory analogue: preallocated frame slots + full credits."""
    return {
        "frames": jnp.zeros((cfg.banks, cfg.frames_per_bank, cfg.words), jnp.int32),
        "credits": jnp.full((cfg.banks,), cfg.frames_per_bank, jnp.int32),
        "head": jnp.zeros((cfg.banks,), jnp.int32),   # next free slot per bank
    }


# ---------------------------------------------------------------------------
# posting
# ---------------------------------------------------------------------------

def post_local(mb: Dict[str, jax.Array], bank: jax.Array,
               frame: jax.Array) -> Dict[str, jax.Array]:
    """Loopback put of one frame into ``bank`` at its head slot.

    A full bank (zero credits) **drops** the frame, mirroring the wire
    protocol where a sender without a credit may not put; without the mask,
    ``dynamic_update_slice`` clamps the out-of-range slot index and silently
    overwrites the bank's last frame while credits go negative.
    """
    slot = mb["head"][bank]
    has_credit = mb["credits"][bank] > 0
    updated = jax.lax.dynamic_update_slice(
        mb["frames"], frame[None, None, :],
        (bank, slot, 0))
    delta = has_credit.astype(jnp.int32)
    return {
        "frames": jnp.where(has_credit, updated, mb["frames"]),
        "credits": mb["credits"].at[bank].add(-delta),
        "head": mb["head"].at[bank].add(delta),
    }


def ring_put(frame_block: jax.Array, axis_name: str, shift: int = 1) -> jax.Array:
    """One-sided put to the ring neighbor (RDMA-put analogue).

    Must run inside shard_map. frame_block: (..., W) frames this device
    sends; returns the frames that LANDED here from the neighbor.
    """
    n = jax.lax.axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return jax.lax.ppermute(frame_block, axis_name, perm)


def alltoall_put(frame_blocks: jax.Array, axis_name: str) -> jax.Array:
    """Scatter per-destination frame blocks (n, N, W) -> arrivals (n, N, W).

    arrivals[j] = frames rank j addressed to me. The paper's injection-rate
    shape with every rank streaming to every other.
    """
    return jax.lax.all_to_all(frame_blocks, axis_name, 0, 0, tiled=False)


# ---------------------------------------------------------------------------
# draining (execute-on-arrival)
# ---------------------------------------------------------------------------

def drain_frames(frames: jax.Array,
                 dispatch: Callable[[jax.Array], jax.Array],
                 result_words: int) -> jax.Array:
    """Execute every frame slot (invalid slots produce zeros).

    frames: (..., N, W) -> results (..., N, result_words). This is the
    receiver thread's wake-and-execute loop, vectorized.
    """
    flat = frames.reshape(-1, frames.shape[-1])
    out = jax.vmap(dispatch)(flat)
    return out.reshape(frames.shape[:-1] + (result_words,))


def drain_mailbox(mb: Dict[str, jax.Array],
                  dispatch: Callable[[jax.Array], jax.Array],
                  cfg: MailboxConfig) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Drain all banks: execute, clear, restore credits (bank-granular)."""
    results = drain_frames(mb["frames"], dispatch,
                           _result_words(dispatch, cfg))
    cleared = {
        "frames": jnp.zeros_like(mb["frames"]),
        "credits": jnp.full_like(mb["credits"], cfg.frames_per_bank),
        "head": jnp.zeros_like(mb["head"]),
    }
    return results, cleared


def _result_words(dispatch, cfg: MailboxConfig) -> int:
    probe = jax.eval_shape(dispatch, jax.ShapeDtypeStruct((cfg.words,), jnp.int32))
    return probe.shape[0]


# ---------------------------------------------------------------------------
# wait loops: WFE vs spin-poll (paper §VII-D)
# ---------------------------------------------------------------------------

def spin_wait_poll(frames: jax.Array, spec: FrameSpec,
                   max_spins: int = 1 << 20) -> Tuple[jax.Array, jax.Array]:
    """Software spin-poll on the SIG word of slot 0 (the 'Polling' baseline).

    Returns (spins_executed, found). In interpret/CPU tests the frame is
    already delivered, so this measures the poll-iteration cost structure;
    the op count per spin is the cycle proxy of Fig. 13/14.
    """
    sig_off = spec.offsets()["sig"]

    def cond(carry):
        spins, found = carry
        return (~found) & (spins < max_spins)

    def body(carry):
        spins, _ = carry
        from repro.core.message import SIG_MAGIC
        found = frames[0, sig_off] == SIG_MAGIC
        return spins + 1, found

    spins, found = jax.lax.while_loop(cond, body, (jnp.int32(0), jnp.bool_(False)))
    return spins, found


def wfe_wait(frames: jax.Array, spec: FrameSpec) -> Tuple[jax.Array, jax.Array]:
    """Hardware-wait analogue: a DMA-semaphore wait consumes ZERO spin
    iterations — the kernel blocks until the transport signals completion
    (Pallas ``dma.wait()``; Arm WFE in the paper). In the jnp reference the
    wait is a single check because delivery already happened-before."""
    sig_off = spec.offsets()["sig"]
    from repro.core.message import SIG_MAGIC
    found = frames[0, sig_off] == SIG_MAGIC
    return jnp.int32(0), found
