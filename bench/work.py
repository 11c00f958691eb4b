"""The work that serving needs, counted from the requests' token positions.

Nothing here reads the program's shapes (its chunk width, block size or
grid): a row of a step is ``(start, n)``, the ``n`` tokens at positions
``start .. start + n - 1`` that the step fed for one request. So a later
program that does the same attention another way is held to the same work,
and a share of a peak cannot pass 100% because a count went stale.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Iterable, Tuple

from spec import BENCH

BF16 = 2


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of one chip of ``device_kind``; an unknown kind
    is an error, never a default."""
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)["chips"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def attention_context(start: int, n: int) -> int:
    """Keys the queries of one row see under the causal mask: the token at
    position p sees p + 1 keys."""
    return n * start + n * (n + 1) // 2


def kernel_call(g, rows: Iterable[Tuple[int, int]]
                ) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) one layer's paged-attention call needs for the
    rows ``(start, n)`` of a step: q.k and p.v over each query's causal
    context, each row's resident k and v read once, q read and the output
    written once."""
    flops = 0.0
    nbytes = 0.0
    for start, n in rows:
        if n <= 0:
            continue
        flops += 4.0 * g.heads * g.head_dim * attention_context(start, n)
        nbytes += 2.0 * (start + n) * g.kv_heads * g.head_dim * BF16
        nbytes += 2.0 * n * g.heads * g.head_dim * BF16
    return flops, nbytes


def kernel_min_seconds(g, rows: Iterable[Tuple[int, int]],
                       peak: Dict[str, float]) -> float:
    """The least time one layer's call can take on the chip: the larger of
    its FLOPs over the peak rate and its bytes over the HBM bandwidth."""
    flops, nbytes = kernel_call(g, rows)
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


def step_flops(g, rows: Iterable[Tuple[int, int]], emitted: int,
               layer_params: int) -> float:
    """Model FLOPs a step needs: every fed prompt or output token through
    every layer's matmuls (``layer_params`` weights, the block's
    ``layer_matmul_params``) and its causal attention, and the head for
    the ``emitted`` tokens whose logits are used. Padding counts nothing."""
    per_token = 2.0 * g.layers * layer_params
    flops = 0.0
    for start, n in rows:
        if n <= 0:
            continue
        flops += n * per_token
        flops += g.layers * 4.0 * g.heads * g.head_dim * attention_context(
            start, n)
    return flops + emitted * 2.0 * g.d_model * g.vocab
