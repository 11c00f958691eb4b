"""The dense decoder block: pre-norm GQA (or MHA) attention with full
rotary, then a gated SiLU MLP, RMSNorm throughout."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict


@dataclasses.dataclass(frozen=True)
class Geometry:
    name: str
    layers: int
    d_model: int
    d_ff: int
    vocab: int
    heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float
    norm_eps: float
    tied: bool


def geometry(cfg: Dict[str, Any], max_len: int) -> Geometry:
    """The published keys of ``cfg`` as the block the benchmark runs.
    Refuses what neither the program's paged path nor the reference
    models, instead of running something else under the model's name."""
    if cfg.get("hidden_act") != "silu":
        raise ValueError(f"{cfg['name']}: hidden_act {cfg.get('hidden_act')!r}"
                         " is not the gated SiLU MLP the reference covers")
    if cfg.get("rope_scaling"):
        raise ValueError(f"{cfg['name']}: rope_scaling is not modelled")
    if cfg.get("attention_bias"):
        raise ValueError(f"{cfg['name']}: attention biases are not modelled")
    # the last position fed is max_len - 2 (the final token is not fed
    # back); the window binds once a query sits ``window`` positions past
    # the first key
    window = cfg.get("sliding_window")
    if window is not None and max_len - 2 >= window:
        raise ValueError(f"{cfg['name']}: a sliding window of {window} binds "
                         f"at max_len {max_len}; only full attention is run")
    heads, d = cfg["num_attention_heads"], cfg["hidden_size"]
    return Geometry(
        name=cfg["name"], layers=cfg["num_hidden_layers"], d_model=d,
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"], heads=heads,
        kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or d // heads,
        rope_theta=float(cfg["rope_theta"]), norm_eps=float(cfg["rms_norm_eps"]),
        tied=bool(cfg["tie_word_embeddings"]))


def kv_bytes_per_token(g: Geometry) -> int:
    """bf16 k and v of one token in every layer."""
    return 2 * g.layers * g.kv_heads * g.head_dim * 2


def layer_matmul_params(g: Geometry) -> int:
    """Weights one token multiplies by in one layer (q, k, v, o, gate, up,
    down)."""
    attn = g.d_model * g.head_dim * (2 * g.heads + 2 * g.kv_heads)
    return attn + 3 * g.d_model * g.d_ff


def model_config(g: Geometry, dtype: str):
    """The program's ``ModelConfig`` of this block."""
    from repro.configs.base import AttentionConfig, ModelConfig
    return ModelConfig(
        name=g.name, family="dense", num_layers=g.layers,
        d_model=g.d_model, d_ff=g.d_ff, vocab_size=g.vocab,
        attention=AttentionConfig(
            kind="gqa", num_heads=g.heads, num_kv_heads=g.kv_heads,
            head_dim=g.head_dim, rope_theta=g.rope_theta),
        norm_eps=g.norm_eps, tie_embeddings=g.tied, act="silu",
        mlp_gated=True, dtype=dtype, remat="none")
