"""The DeepSeek-V2 block: latent attention (MLA, no query compression) with
YaRN rotary, then a dense gated SiLU MLP in the leading layers and a routed
and shared expert layer in the rest, RMSNorm throughout.

The configuration may hold a share of the routed experts (``reduced``
names ``n_routed_experts``, ``published`` gives the router's width): the
program and the reference then compute experts ``[0, n_routed_experts)``
of the router's ``published`` count, and leave the rest out alike.

The work functions (``kernel_min_seconds``, ``step_flops``) count from the
rows' token positions, never from the program's shapes (``bench/work.py``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterable, Tuple

from work import BF16, attention_context


@dataclasses.dataclass(frozen=True)
class Geometry:
    name: str
    layers: int
    d_model: int
    d_ff: int                   # the leading dense layers' MLP
    vocab: int
    heads: int
    kv_heads: int               # 1: one latent row a token serves every head
    head_dim: int               # the latent row: kv_lora_rank + qk_rope
    kv_lora_rank: int
    qk_nope: int
    qk_rope: int
    v_head_dim: int
    dense_layers: int
    experts: int                # the router's outputs
    experts_held: Tuple[int, int]
    top_k: int
    expert_ff: int
    shared: int
    norm_topk_prob: bool
    routed_scaling: float
    rope_theta: float
    yarn: Tuple[float, int, float, float, float, float]
    norm_eps: float
    tied: bool


def geometry(cfg: Dict[str, Any], max_len: int) -> Geometry:
    """The published keys of ``cfg`` as the block the benchmark runs.
    Refuses what neither the program's paged path nor the reference
    models, instead of running something else under the model's name."""
    name = cfg["name"]
    refuse = {
        "hidden_act": cfg.get("hidden_act") != "silu",
        "scoring_func": cfg.get("scoring_func") != "softmax",
        "topk_method": cfg.get("topk_method") != "greedy",
        "q_lora_rank": cfg.get("q_lora_rank") is not None,
        "n_group": cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1,
        "attention_bias": bool(cfg.get("attention_bias")),
        "moe_layer_freq": cfg.get("moe_layer_freq", 1) != 1,
        "rope_scaling": (cfg.get("rope_scaling") or {}).get("type") != "yarn",
    }
    bad = [k for k, v in refuse.items() if v]
    if bad:
        raise ValueError(f"{name}: {bad} are not what the block models "
                         "(gated SiLU MLPs, a greedy softmax router over "
                         "one group, no query compression, no biases, an "
                         "expert layer after every leading dense layer, "
                         "YaRN rotary)")
    experts = cfg["n_routed_experts"]
    if "n_routed_experts" in cfg.get("reduced", []):
        experts = cfg["published"]["n_routed_experts"]
    ys = cfg["rope_scaling"]
    r, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return Geometry(
        name=name, layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        heads=cfg["num_attention_heads"], kv_heads=1, head_dim=r + rope,
        kv_lora_rank=r, qk_nope=cfg["qk_nope_head_dim"], qk_rope=rope,
        v_head_dim=cfg["v_head_dim"],
        dense_layers=cfg["first_k_dense_replace"], experts=experts,
        experts_held=(0, cfg["n_routed_experts"]),
        top_k=cfg["num_experts_per_tok"],
        expert_ff=cfg["moe_intermediate_size"],
        shared=cfg["n_shared_experts"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        routed_scaling=float(cfg["routed_scaling_factor"]),
        rope_theta=float(cfg["rope_theta"]),
        yarn=(float(ys["factor"]), int(ys["original_max_position_embeddings"]),
              float(ys["beta_fast"]), float(ys["beta_slow"]),
              float(ys["mscale"]), float(ys["mscale_all_dim"])),
        norm_eps=float(cfg["rms_norm_eps"]),
        tied=bool(cfg["tie_word_embeddings"]))


def latent_lanes(g: Geometry) -> int:
    """Lanes the pool stores a token's latent row in: kv_lora_rank +
    qk_rope, padded to whole 128-lane tiles."""
    return -(-g.head_dim // 128) * 128


def kv_bytes_per_token(g: Geometry) -> int:
    """bf16 latent rows of one token in every layer, as the pool stores
    them (padding lanes included)."""
    return g.layers * latent_lanes(g) * BF16


def attn_params(g: Geometry) -> int:
    """Weights one token multiplies by in one layer's absorbed MLA: q,
    the kv down-projection, W_UK into the latent, W_UV out of it, o."""
    h = g.heads
    return (g.d_model * h * (g.qk_nope + g.qk_rope)
            + g.d_model * (g.kv_lora_rank + g.qk_rope)
            + h * g.qk_nope * g.kv_lora_rank
            + h * g.kv_lora_rank * g.v_head_dim
            + h * g.v_head_dim * g.d_model)


def held_share(g: Geometry) -> float:
    lo, hi = g.experts_held
    return (hi - lo) / g.experts


def layer_matmul_params(g: Geometry) -> float:
    """Weights one token multiplies by in one layer, averaged over the
    layers: attention, the dense MLP in the leading layers, and in the
    expert layers the router, the shared experts and on average
    ``top_k`` times the held share of the routed experts."""
    moe = (g.d_model * g.experts
           + 3 * g.d_model * g.expert_ff * (g.shared + g.top_k * held_share(g)))
    ffn = (g.dense_layers * 3 * g.d_model * g.d_ff
           + (g.layers - g.dense_layers) * moe) / g.layers
    return attn_params(g) + ffn


def model_config(g: Geometry, dtype: str):
    """The program's ``ModelConfig`` of this block."""
    from repro.configs.base import (AttentionConfig, ModelConfig, MoEConfig,
                                    YarnConfig)
    factor, orig, fast, slow, ms, ms_all = g.yarn
    return ModelConfig(
        name=g.name, family="moe", num_layers=g.layers, d_model=g.d_model,
        d_ff=g.d_ff, vocab_size=g.vocab,
        attention=AttentionConfig(
            kind="mla", num_heads=g.heads, num_kv_heads=g.heads,
            head_dim=g.qk_nope + g.qk_rope, kv_lora_rank=g.kv_lora_rank,
            qk_nope_head_dim=g.qk_nope, qk_rope_head_dim=g.qk_rope,
            v_head_dim=g.v_head_dim, rope_theta=g.rope_theta,
            yarn=YarnConfig(factor=factor,
                            original_max_position_embeddings=orig,
                            beta_fast=fast, beta_slow=slow, mscale=ms,
                            mscale_all_dim=ms_all)),
        moe=MoEConfig(
            num_experts=g.experts, top_k=g.top_k, expert_ff=g.expert_ff,
            num_shared=g.shared, shared_ff=g.expert_ff,
            first_dense_layers=g.dense_layers,
            norm_topk_prob=g.norm_topk_prob,
            routed_scaling_factor=g.routed_scaling,
            experts_held=g.experts_held),
        norm_eps=g.norm_eps, tie_embeddings=g.tied, act="silu",
        mlp_gated=True, dtype=dtype, remat="none")


# ---------------------------------------------------------------------------
# work
# ---------------------------------------------------------------------------

def kernel_call(g: Geometry, rows: Iterable[Tuple[int, int]]
                ) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) one layer's latent attention needs for the rows
    ``(start, n)`` of a step: for every head, q.k over the latent's
    kv_lora_rank + qk_rope lanes and p.v over its kv_lora_rank, over each
    query's causal context; each row's latent context read once at those
    lanes, the absorbed q read and the output written once."""
    flops = nbytes = 0.0
    qk, pv = g.kv_lora_rank + g.qk_rope, g.kv_lora_rank
    for start, n in rows:
        if n <= 0:
            continue
        flops += 2.0 * g.heads * (qk + pv) * attention_context(start, n)
        nbytes += (start + n) * qk * BF16
        nbytes += n * g.heads * (qk + pv) * BF16
    return flops, nbytes


def kernel_min_seconds(g: Geometry, rows: Iterable[Tuple[int, int]],
                       peak: Dict[str, float]) -> float:
    """The least time one layer's latent-attention call can take: the
    larger of its FLOPs over the peak rate and its bytes over the HBM
    bandwidth."""
    flops, nbytes = kernel_call(g, rows)
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


def step_flops(g: Geometry, rows: Iterable[Tuple[int, int]],
               emitted: int) -> float:
    """Model FLOPs a step needs: every fed token through every layer's
    matmuls (``layer_matmul_params``: the held share's expected experts,
    never the masked ones) and its latent attention, and the head for the
    ``emitted`` tokens. Padding counts nothing."""
    per_token = 2.0 * g.layers * layer_matmul_params(g)
    qk, pv = g.kv_lora_rank + g.qk_rope, g.kv_lora_rank
    flops = 0.0
    for start, n in rows:
        if n <= 0:
            continue
        flops += n * per_token
        flops += (g.layers * 2.0 * g.heads * (qk + pv)
                  * attention_context(start, n))
    return flops + emitted * 2.0 * g.d_model * g.vocab

