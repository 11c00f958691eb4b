"""Architecture registry: --arch <id> resolution + per-arch shape applicability."""
from __future__ import annotations

from typing import Callable, Dict, Tuple

from repro.configs import (
    deepseek_v2_lite_16b,
    gemma3_4b,
    granite_20b,
    hubert_xlarge,
    hymba_1p5b,
    llama32_1b,
    mamba_130m,
    olmoe_1b_7b,
    qwen2_vl_72b,
    stablelm_3b,
    xlstm_1p3b,
)
from repro.configs.base import SHAPES, ModelConfig, ShapeConfig

_MODULES = (
    gemma3_4b, granite_20b, llama32_1b, stablelm_3b, deepseek_v2_lite_16b,
    olmoe_1b_7b, hymba_1p5b, xlstm_1p3b, mamba_130m, hubert_xlarge,
    qwen2_vl_72b,
)

ARCHS: Dict[str, Callable[[], ModelConfig]] = {m.ARCH_ID: m.config for m in _MODULES}
SMOKES: Dict[str, Callable[[], ModelConfig]] = {m.ARCH_ID: m.smoke for m in _MODULES}

# long_500k is only runnable with sub-quadratic attention. Pure full-attention
# archs skip it (DESIGN.md §5). gemma3 runs it (5:1 sliding-window layers);
# hymba (hybrid) and xlstm/mamba (recurrent) run it.
_LONG_OK = {"gemma3-4b", "hymba-1.5b", "xlstm-1.3b", "mamba-130m"}
# Encoder-only archs have no decode step.
_ENCODER_ONLY = {"hubert-xlarge"}


def default_cache_backend(cfg: ModelConfig) -> str:
    """The serving Engine's default sequence-state backend per model family.

    Recurrent stacks (xLSTM, pure SSM) carry constant-size state — the
    recurrent backend serves them exactly AND preempts for free. Archs the
    paged pool cannot hold (hybrid attn+SSM, mrope position streams) fall
    back to the contiguous slots rows. GQA archs get the paged pool, and so
    do MLA archs, whose latents it holds one leaf a layer (docs/serving.md
    has the full backend table).
    """
    if cfg.xlstm is not None or (cfg.ssm is not None and cfg.attention is None):
        return "recurrent"
    a = cfg.attention
    if cfg.parallel_ssm_attn or (a is not None and a.mrope):
        return "slots"
    return "paged"


def get_config(arch: str) -> ModelConfig:
    if arch not in ARCHS:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(ARCHS)}")
    return ARCHS[arch]()


def get_smoke(arch: str) -> ModelConfig:
    return SMOKES[arch]()


def cell_status(arch: str, shape_name: str) -> Tuple[bool, str]:
    """(runnable, reason) for an (arch x shape) cell."""
    shape = SHAPES[shape_name]
    if arch in _ENCODER_ONLY and shape.kind == "decode":
        return False, "encoder-only: no decode step"
    if shape_name == "long_500k" and arch not in _LONG_OK:
        return False, "pure full-attention: long_500k needs sub-quadratic attention"
    return True, ""


def all_cells():
    """Yield (arch, shape, runnable, reason) for the full 40-cell matrix."""
    for arch in ARCHS:
        for shape_name in SHAPES:
            ok, why = cell_status(arch, shape_name)
            yield arch, shape_name, ok, why
