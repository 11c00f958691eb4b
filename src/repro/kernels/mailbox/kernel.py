"""Reactive-mailbox Pallas kernels — the paper's RDMA transport (Fig. 1),
TPU-native.

The paper's mechanisms and their exact analogues here:

  one-sided RDMA put        -> ``pltpu.make_async_remote_copy`` to the ring
                               neighbor (``device_id`` over the shard_map axis)
  pinned mailbox memory     -> the kernel's output ref; ``stash=True`` places
                               it in VMEM (the NIC-stashes-to-LLC path of
                               §VII-B), ``stash=False`` in ANY/HBM (the DRAM
                               path)
  signal-word wait (WFE)    -> ``rdma.wait_recv()`` — a hardware DMA-semaphore
                               block, zero spin iterations
  signal-word wait (poll)   -> a ``lax.while_loop`` reading the SIG word of
                               the last frame from the VMEM mailbox, counting
                               spins (the cycle proxy of Fig. 13/14)
  execute-on-arrival        -> ``handler="sum"`` fuses the Server-Side Sum jam
                               into the same kernel, consuming frames from
                               VMEM before they ever reach HBM (stashing)

Standalone handler kernels (the Local Function path — code resident,
payload arrives):

  ``sum_drain_pallas``      — Server-Side Sum over an (N, W) frame block
  ``indirect_put_pallas``   — Indirect Put: key -> hashed offset (indirected
                              through the GOT-resolved heap base in SMEM),
                              payload row stored into the server heap
                              (aliased in/out: the server's memory mutates)
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu
import jax.numpy as jnp


# Mirrors core.message constants (kept literal: kernels are dependency-free).
SIG_MAGIC = 0x516A_22
MAX_SPINS = 1 << 20


# ---------------------------------------------------------------------------
# ring put (+ optional fused sum handler)
# ---------------------------------------------------------------------------

def _mailbox_kernel(frames_ref, out_ref, spins_ref, sums_ref, send_sem,
                    recv_sem, *, axis_name: str, shift: int, wait: str,
                    stash: bool, handler: Optional[str], sig_off: int,
                    usr_off: int, payload_words: int, n_frames: int):
    my = jax.lax.axis_index(axis_name)
    n = jax.lax.axis_size(axis_name)
    dst = jax.lax.rem(my + shift, n)
    rdma = pltpu.make_async_remote_copy(
        src_ref=frames_ref, dst_ref=out_ref,
        send_sem=send_sem, recv_sem=recv_sem,
        device_id=(dst,), device_id_type=pl.DeviceIdType.MESH)
    rdma.start()
    rdma.wait_send()

    if wait == "wfe" or not stash:
        # Hardware wait: the DMA semaphore blocks until the put lands.
        # Zero spin iterations — the WFE analogue.
        rdma.wait_recv()
        spins_ref[0, 0] = jnp.int32(0)
    else:
        # Spin-poll on the SIG word of the last frame (paper's Polling
        # baseline). wait_recv first for interpret-mode happened-before;
        # the loop then counts its wait iterations — the cycle proxy.
        rdma.wait_recv()

        def cond(c):
            s, found = c
            return jnp.logical_and(jnp.logical_not(found), s < MAX_SPINS)

        def body(c):
            s, _ = c
            found = out_ref[n_frames - 1, sig_off] == SIG_MAGIC
            return s + 1, found

        spins, _ = jax.lax.while_loop(
            cond, body, (jnp.int32(0), jnp.bool_(False)))
        spins_ref[0, 0] = spins

    if handler == "sum":
        # Execute-on-arrival, fused: the Server-Side Sum jam consumes the
        # frames straight out of the VMEM mailbox (the stash win).
        usr = out_ref[:, usr_off:usr_off + payload_words]
        sums_ref[:, 0] = jnp.sum(usr, axis=1, dtype=jnp.int32)


def mailbox_put_pallas(
    frames: jax.Array, *, axis_name: str, shift: int = 1, wait: str = "wfe",
    stash: bool = True, handler: Optional[str] = None, sig_off: int,
    usr_off: int, payload_words: int, interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """One-sided ring put of an (N, W) int32 frame block; shard_map-only.

    Returns (arrivals (N, W), spins (1, 1) int32, sums (N, 1) int32 | None).
    ``stash=True``: mailbox in VMEM (poll-able, handler-fusable).
    ``stash=False``: mailbox in ANY/HBM (semaphore wait only; drain with
    ``sum_drain_pallas`` afterwards — the extra HBM round trip).
    """
    n_frames, words = frames.shape
    mem = pltpu.VMEM if stash else pl.ANY
    out_shapes = [
        jax.ShapeDtypeStruct((n_frames, words), jnp.int32),   # arrivals
        jax.ShapeDtypeStruct((1, 1), jnp.int32),              # spins
        jax.ShapeDtypeStruct((n_frames, 1), jnp.int32),       # sums
    ]
    out_specs = [
        pl.BlockSpec(memory_space=mem),
        pl.BlockSpec(memory_space=pltpu.VMEM),
        pl.BlockSpec(memory_space=pltpu.VMEM),
    ]
    kernel = functools.partial(
        _mailbox_kernel, axis_name=axis_name, shift=shift, wait=wait,
        stash=stash, handler=handler, sig_off=sig_off, usr_off=usr_off,
        payload_words=payload_words, n_frames=n_frames)
    # Remote DMAs need the TPU-semantics interpreter (InterpretParams), not
    # the generic Pallas interpreter — the latter cannot discharge
    # mesh-logical device ids.
    interp = pltpu.InterpretParams() if interpret else False
    arrivals, spins, sums = pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(memory_space=mem)],
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=[pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=7),
        interpret=interp,
    )(frames)
    return arrivals, spins, (sums if handler == "sum" else None)


# ---------------------------------------------------------------------------
# Server-Side Sum drain (Local Function handler / non-stash second stage)
# ---------------------------------------------------------------------------

def _sum_kernel(frames_ref, sums_ref, *, usr_off: int, payload_words: int):
    usr = frames_ref[:, usr_off:usr_off + payload_words]
    sums_ref[:, 0] = jnp.sum(usr, axis=1, dtype=jnp.int32)


def _drain_geometry(n: int, block_n: int) -> Tuple[int, int]:
    """(tile rows, padded N). N pads up to a tile multiple instead of
    degrading the tile: the old linear search for a divisor of N walked
    ``block_n`` down to 1 for prime N, so a 127-frame drain ran a 127-step
    grid of width-1 tiles. Tiles stay sublane-aligned (multiples of 8),
    including for caller-passed ``block_n`` that isn't one."""
    aligned = -(-n // 8) * 8
    bn = max(8, min(block_n, aligned) // 8 * 8)
    return bn, -(-n // bn) * bn


def sum_drain_pallas(frames: jax.Array, *, usr_off: int, payload_words: int,
                     block_n: int = 128, interpret: bool = False) -> jax.Array:
    """Server-Side Sum over (N, W) frames -> (N, 1) sums (HBM -> VMEM tile).

    N that doesn't divide into ``block_n`` tiles is zero-padded up to the
    next tile multiple — zero rows sum to zero and are sliced off, so no
    in-kernel mask is needed.
    """
    n, w = frames.shape
    bn, n_pad = _drain_geometry(n, block_n)
    if n_pad != n:
        frames = jnp.pad(frames, ((0, n_pad - n), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_sum_kernel, usr_off=usr_off,
                          payload_words=payload_words),
        grid=(n_pad // bn,),
        in_specs=[pl.BlockSpec((bn, w), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),
        interpret=interpret,
    )(frames)
    return out[:n]


# ---------------------------------------------------------------------------
# Indirect Put (paper Fig. 4)
# ---------------------------------------------------------------------------

def _indirect_put_kernel(got_ref, frames_ref, table_ref, heap_ref,
                         table_out, heap_out, *, usr_off: int,
                         payload_words: int, n_frames: int, slots: int):
    # Aliased in/out: start from the current server state.
    table_out[...] = table_ref[...]
    heap_out[...] = heap_ref[...]
    got_base = got_ref[0]                      # receiver-resolved GOT symbol

    def body(i, _):
        key = frames_ref[i, usr_off]
        idx = jnp.remainder(jnp.remainder(key, slots) + got_base, slots)
        data = frames_ref[i, usr_off + 1:usr_off + payload_words]
        table_out[pl.ds(idx, 1), :] = jnp.stack([key, idx])[None, :]
        heap_out[pl.ds(idx, 1), :] = data[None, :]
        return 0

    jax.lax.fori_loop(0, n_frames, body, 0)


def indirect_put_pallas(frames: jax.Array, table: jax.Array, heap: jax.Array,
                        got: jax.Array, *, usr_off: int, payload_words: int,
                        interpret: bool = False
                        ) -> Tuple[jax.Array, jax.Array]:
    """Apply (N, W) indirect-put frames to the server's (table, heap).

    table: (slots, 2) int32 [key, offset]; heap: (slots, PW-1) int32;
    got: (G,) int32 — receiver-resident symbol values (SMEM scalars), slot 0
    is the heap base indirection. Returns the updated (table, heap).
    """
    n, w = frames.shape
    slots = table.shape[0]
    return pl.pallas_call(
        functools.partial(_indirect_put_kernel, usr_off=usr_off,
                          payload_words=payload_words, n_frames=n,
                          slots=slots),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(table.shape, jnp.int32),
            jax.ShapeDtypeStruct(heap.shape, jnp.int32),
        ],
        interpret=interpret,
    )(got, frames, table, heap)
