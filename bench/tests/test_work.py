"""Work counts against brute force, and the peaks table."""
import pytest

import work
from blocks.dense import Geometry, layer_matmul_params

G = Geometry("g", layers=2, d_model=64, d_ff=96, vocab=100, heads=4,
             kv_heads=2, head_dim=16, rope_theta=1e4, norm_eps=1e-5, tied=False)


def test_attention_context_is_the_causal_count():
    for start, n in [(0, 1), (0, 8), (37, 1), (100, 8)]:
        assert work.attention_context(start, n) == sum(
            p + 1 for p in range(start, start + n))


def test_kernel_call_counts_each_rows_kv_once():
    flops, nbytes = work.kernel_call(G, [(10, 1), (0, 8), (5, 0)])
    keys = 11 + sum(range(1, 9))
    assert flops == 4 * 4 * 16 * keys
    kv = 2 * (11 + 8) * 2 * 16 * 2
    qo = 2 * (1 + 8) * 4 * 16 * 2
    assert nbytes == kv + qo


def test_step_flops_leave_padding_out():
    rows = [(3, 1), (0, 5)]
    per_layer = layer_matmul_params(G)
    per_tok = 2 * G.layers * per_layer
    attn = G.layers * 4 * G.heads * G.head_dim * (4 + 15)
    assert (work.step_flops(G, rows, emitted=1, layer_params=per_layer)
            == 6 * per_tok + attn + 2 * 64 * 100)
    assert work.step_flops(G, [(0, 0)], emitted=0, layer_params=per_layer) == 0


def test_unknown_device_kind_is_an_error():
    assert work.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
