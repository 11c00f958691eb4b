"""The comparison that decides ``correct``.

Once the window has closed and the program's weights and pool are freed, a
sample of the requests the window finished, drawn from the seed and always
holding the longest, goes through the float32 reference of the
configuration's block (``bench/blocks/<block>/reference.py``): each prompt
with its served tokens, in one pass. At every position where the program
served a token, the number compared is its gap: how far the reference's
logit of the served token lies below the reference's best logit there, in
standard deviations of that position's logits. The run's reading is the
widest gap over the sample. A sound bf16 program serves the reference's
best token or a near-tie; a wrong token, a position or masking fault, or a
lower precision lands far below.

The control reads the same gap for the token that the reference computed
in float8 (``quant="fp8"``) puts first at each of those positions
(``harness.run_seed(control=True)`` judges it against the cell's limit).
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional

import numpy as np

from spec import Cell

SAMPLE = 4                  # requests compared per run, the longest among them

_CUSTOM_CALL = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*custom_call_target=\"tpu_custom_call\"",
    re.M)


def custom_call_names(hlo_text: str) -> List[str]:
    """Names of the Mosaic kernels (``tpu_custom_call``) in compiled HLO."""
    return _CUSTOM_CALL.findall(hlo_text)


def pick(finished: list, seed: int, k: int = SAMPLE) -> list:
    """``k`` finished requests drawn from ``seed``: the longest (prompt
    plus output) and ``k - 1`` others."""
    if not finished:
        return []
    size = lambda r: len(r.planned.prompt) + len(r.req.out_tokens)
    order = sorted(finished, key=lambda r: (-size(r), r.planned.rid))
    rest = order[1:]
    rng = np.random.default_rng(seed)
    idx = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [order[0]] + [rest[i] for i in sorted(idx)]


def layout(cell: Cell, sample: list, k: int = SAMPLE):
    """Token ids (k, P) — each prompt followed by its served tokens but the
    last — and the served token asked at each position (k, P), -1 where
    nothing was served. Rows past the sample are empty."""
    P = -(-cell.max_len // 128) * 128
    tokens = np.zeros((k, P), np.int32)
    served = np.full((k, P), -1, np.int32)
    for b, r in enumerate(sample):
        prompt, out = r.planned.prompt, list(r.req.out_tokens)
        seq = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
        tokens[b, :len(seq)] = seq
        L = len(prompt)
        served[b, L - 1:L - 1 + len(out)] = out
    return tokens, served


def compare(cell: Cell, seed: int, sample: list, control: bool = False
            ) -> Dict[str, Optional[float]]:
    """The widest gap of the served tokens over ``sample`` (``max_gap``),
    the tokens compared, and with ``control`` the widest gap of the fp8
    reference's first choices at the same positions (``control_gap``)."""
    g = cell.geometry
    reference = cell.block("reference")
    tokens, served = layout(cell, sample, max(SAMPLE, len(sample)))
    asked = [served]
    if control:
        _, top8 = reference.forward_stats(g, seed, tokens, served[None],
                                          quant="fp8")
        asked.append(np.where(served >= 0, top8, -1))
    gap, _ = reference.forward_stats(g, seed, tokens, np.stack(asked))
    mask = served >= 0
    out = {"max_gap": float(gap[0][mask].max()) if mask.any() else None,
           "tokens": int(mask.sum())}
    if control:
        out["control_gap"] = float(gap[1][mask].max()) if mask.any() else None
    return out
