"""The ``mla_moe`` block at a CPU size: its geometry, its work counts by
hand, and a cell served through the harness, judged correct, and the fp8
control judged not."""
import dataclasses
import json
import os

import pytest

import harness
import spec
import trace_spans as ts
from blocks import mla_moe
from spec import Cell

FULL = spec.read_json(os.path.join(spec.BENCH, "configs",
                                   "deepseek-v2-lite-ep4.json"))
G = mla_moe.geometry(FULL, 4607)
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_geometry_holds_the_published_widths_and_the_share():
    assert (G.layers, G.d_model, G.vocab, G.heads) == (27, 2048, 102400, 16)
    assert (G.kv_lora_rank, G.qk_nope, G.qk_rope, G.v_head_dim) == (
        512, 128, 64, 128)
    assert (G.experts, G.experts_held, G.top_k) == (64, (0, 16), 6)
    assert G.yarn == (40.0, 4096, 32.0, 1.0, 0.707, 0.707)
    assert mla_moe.kv_bytes_per_token(G) == 27 * 640 * 2


@pytest.mark.parametrize("key,value", [
    ("scoring_func", "sigmoid"), ("topk_method", "group_limited_greedy"),
    ("q_lora_rank", 1536), ("n_group", 8), ("attention_bias", True),
    ("rope_scaling", None)])
def test_geometry_refuses_what_it_does_not_model(key, value):
    with pytest.raises(ValueError):
        mla_moe.geometry(dict(FULL, **{key: value}), 4607)


def test_kernel_call_by_hand():
    """One decode row at 100 resident tokens and one 8-token chunk from 0:
    q.k over 576 lanes and p.v over 512 for 16 heads, each row's context
    read once at 576 lanes, q (576 + 512 lanes a head... as counted: q at
    576 and out at 512) once."""
    flops, nbytes = mla_moe.kernel_call(G, [(99, 1), (0, 8), (7, 0)])
    keys = 100 + (1 + 2 + 3 + 4 + 5 + 6 + 7 + 8)
    assert flops == 2 * 16 * (576 + 512) * keys
    assert nbytes == (100 + 8) * 576 * 2 + 9 * 16 * (576 + 512) * 2
    least = mla_moe.kernel_min_seconds(G, [(99, 1), (0, 8)], PEAK)
    assert least == max(flops / 197e12, nbytes / 819e9)


def test_step_flops_by_hand():
    attn = (2048 * 16 * 192 + 2048 * 576 + 16 * 128 * 512 + 16 * 512 * 128
            + 16 * 128 * 2048)
    dense = 3 * 2048 * 10944
    moe = 2048 * 64 + 3 * 2048 * 1408 * (2 + 6 * 16 / 64)
    per_layer = attn + (dense + 26 * moe) / 27
    assert mla_moe.layer_matmul_params(G) == pytest.approx(per_layer)
    rows = [(99, 1), (0, 3)]
    want = (4 * 2 * 27 * per_layer
            + 27 * 2 * 16 * (576 + 512) * (100 + 6)
            + 2 * 2 * 2048 * 102400)
    assert mla_moe.step_flops(G, rows, emitted=2) == pytest.approx(want)
    assert mla_moe.step_flops(G, [(5, 0)], emitted=0) == 0


TINY = dict(FULL, name="tiny-mla", hidden_size=64, intermediate_size=96,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=16,
            v_head_dim=16, num_attention_heads=4, num_hidden_layers=3,
            moe_intermediate_size=32, n_routed_experts=4, vocab_size=512,
            num_experts_per_tok=3, published={"n_routed_experts": 8},
            serving={"dtype": "bfloat16", "block_size": 8,
                     "pool_bytes": 10 ** 7},
            correct={"max_gap": 0.1})
MIX = {"loop": "closed", "clients": 3, "requests_per_client": 40, "slots": 4,
       "prompt_len": {"median": 24, "sigma": 0.4, "min": 8, "max": 48},
       "output_len": {"median": 6, "sigma": 0.3, "min": 4, "max": 10},
       "limits": {"ttft_ms": {"base": 5000.0, "per_prompt_token": 100.0},
                  "itl_ms": 5000.0},
       "drain_s": 0}


def test_tiny_cell_is_correct_and_the_control_is_not():
    """Served tokens read under the cell's limit, the fp8 control above it,
    and the engine counts the expert share's picks. Readings here (CPU):
    served 0.0, control 0.185; the limit 0.1 lies between."""
    cell = Cell(name="tiny.mla", config=TINY, traffic=MIX)
    server = harness.Server(cell, on_tpu=False)
    result, lines, run = harness.run_seed(server, 2 ** 35 + 9, 8.0,
                                          metrics=[], control=True)
    got = run.check
    assert got["tokens"] > 15
    assert got["max_gap"] <= 0.1 < got["control_gap"], got
    assert result["correct"] is False, (result, lines)
    m = server.engine.metrics()
    assert 0 < m["moe_pairs_here"] < m["moe_pairs"]
    assert m["moe_expert_pairs_max"] > 0
    json.dumps(result)


def test_moe_scopes_are_attributed_by_span_report_moe(monkeypatch):
    import span_report_moe
    hlo = "\n".join([
        "HloModule m", "", "ENTRY %main {",
        '  %a = f32[2] dot(%x, %y), metadata={op_name="jit(s)/moe/'
        'moe.experts/dot_general"}',
        '  %b = f32[2] add(%a, %a), metadata={op_name="jit(s)/moe/add"}',
        "}"])
    assert ts.scope_map(hlo) == {}
    monkeypatch.setattr(ts, "SCOPES", ts.SCOPES + span_report_moe.MOE_SCOPES)
    assert ts.scope_map(hlo) == {"a": "moe.experts", "b": "moe"}


def test_readers_on_a_dense_block_and_on_mla_moe():
    """On a dense block's run ``mla_attn_roofline`` reads nothing and
    ``mfu_pct.mla`` reads as ``mfu_pct``; on ``mla_moe`` both read the
    block's own counts."""
    import tiny
    run = harness.Run(tiny.cell(), 1.0, 0.0, trace={"kernel_s": 1.0,
                                                    "window_s": 1.0,
                                                    "ticks": [0]},
                      ticks=[harness.Tick(0, 1, 1, [(10, 1)], 1)], peak=PEAK)
    assert harness.load_reader("mla_attn_roofline")(run) is None
    assert (harness.load_reader("mfu_pct.mla")(run)
            == harness.load_reader("mfu_pct")(run) > 0)
    run = dataclasses.replace(run, cell=Cell("tiny.mla", TINY, MIX),
                              ticks=[harness.Tick(0, 1, 1, [(10, 1)], 1)],
                              trace={"kernel_s": 1e-6, "window_s": 1.0,
                                     "ticks": [0]})
    g = run.geometry
    want = 100 * 3 * mla_moe.kernel_min_seconds(g, [(10, 1)], PEAK) / 1e-6
    assert harness.load_reader("mla_attn_roofline")(run) == pytest.approx(want)
    flops = mla_moe.step_flops(g, [(10, 1)], 1)
    assert harness.load_reader("mfu_pct.mla")(run) == pytest.approx(
        100 * flops / 197e12)
