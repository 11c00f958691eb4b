"""A tiny cell that the CPU can serve in seconds (Pallas in interpret mode)."""
from spec import Cell

CONFIG = {
    "name": "tiny", "block": "dense", "model_type": "mistral", "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_hidden_layers": 2, "num_key_value_heads": 2, "rms_norm_eps": 1e-5,
    "rope_theta": 10000.0, "sliding_window": None,
    "tie_word_embeddings": False, "vocab_size": 256,
    "serving": {"dtype": "bfloat16", "block_size": 8, "pool_bytes": 160 * 1024},
    "correct": {"max_gap": 0.5},
}

OPEN = {
    "loop": "open", "rate_per_s": 8.0, "slots": 4,
    "prompt_len": {"median": 16, "sigma": 0.5, "min": 6, "max": 40},
    "output_len": {"median": 6, "sigma": 0.4, "min": 3, "max": 12},
    "limits": {"ttft_ms": {"base": 5000.0, "per_prompt_token": 100.0},
               "itl_ms": 5000.0},
    "drain_s": 120,
}

CLOSED = dict(OPEN, loop="closed", clients=3, requests_per_client=40,
              drain_s=0)


def cell(traffic=OPEN, **config) -> Cell:
    return Cell(name="tiny.test", config=dict(CONFIG, **config),
                traffic=traffic)
