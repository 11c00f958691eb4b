"""Teacher-forced logits of a paged engine's step, for checking what it
served against a reference forward.

``teacher_forced_logits(engine, seqs, prompt_lens)`` feeds each sequence
as ``Engine.tick`` feeds a request — the prompt in ``chunk``-wide prefill
steps, then one token per step — through the engine's paged step compiled
with ``emit="logits"``, on the engine's params and a scratch pool of the
same geometry. The engine's own requests and pool are untouched. Sequences
that do not fit the engine's slots and pool at once run in groups.
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.steps import make_paged_serve_step


def _groups(need: List[int], slots: int, num_blocks: int) -> List[List[int]]:
    groups: List[List[int]] = [[]]
    used = 0
    for i, n in enumerate(need):
        if len(groups[-1]) == slots or used + n > num_blocks:
            groups.append([])
            used = 0
        groups[-1].append(i)
        used += n
    return groups


def teacher_forced_logits(engine, seqs: List[List[int]],
                          prompt_lens: List[int]) -> List[np.ndarray]:
    """Row ``i`` of the result, shape ``(len(seqs[i]), V)`` in float32,
    holds the step's logits after each token of ``seqs[i]``; the first
    ``prompt_lens[i]`` tokens are fed as prefill."""
    if engine.cache_kind != "paged":
        raise ValueError("teacher_forced_logits needs cache='paged'")
    if max(len(s) for s in seqs) > engine.max_len:
        raise ValueError("a sequence exceeds the engine's max_len")
    bundle = make_paged_serve_step(
        engine.cfg, engine._run_decode, engine.mesh, slots=engine.slots,
        chunk=engine.chunk, num_blocks=engine.num_blocks,
        block_size=engine.block_size,
        max_blocks_per_seq=engine.max_blocks_per_seq,
        kernel=engine.paged_kernel, emit="logits")
    step = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                   out_shardings=bundle.out_shardings, donate_argnums=(1,))
    need = [-(-len(s) // engine.block_size) for s in seqs]
    out: List[List[np.ndarray]] = [[] for _ in seqs]
    for group in _groups(need, engine.slots, engine.num_blocks):
        cache = engine._fresh_cache()
        tables = np.full((engine.slots, engine.max_blocks_per_seq), -1,
                         np.int32)
        first = 0
        for row, i in enumerate(group):
            tables[row, :need[i]] = np.arange(first, first + need[i])
            first += need[i]
        pos = {i: 0 for i in group}
        while any(pos[i] < len(seqs[i]) for i in group):
            tokens = np.zeros((engine.slots, engine.chunk), np.int32)
            starts = np.zeros((engine.slots,), np.int32)
            n_valid = np.zeros((engine.slots,), np.int32)
            for row, i in enumerate(group):
                if pos[i] < prompt_lens[i]:
                    n = min(engine.chunk, prompt_lens[i] - pos[i])
                else:
                    n = min(1, len(seqs[i]) - pos[i])
                tokens[row, :n] = seqs[i][pos[i]:pos[i] + n]
                starts[row], n_valid[row] = pos[i], n
            logits, cache = step(engine.params, cache, jnp.asarray(tokens),
                                 jnp.asarray(tables), jnp.asarray(starts),
                                 jnp.asarray(n_valid))
            logits = np.asarray(logits)
            for row, i in enumerate(group):
                if n_valid[row]:
                    out[i].append(logits[row, :n_valid[row]])
                pos[i] += int(n_valid[row])
    return [np.concatenate(rows) for rows in out]
