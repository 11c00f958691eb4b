"""The check tells a wrong program from a sound one, at a CPU-sized cell.

The control is the reference in float8, the precision below the bf16 that
the configurations state: at every position where the program served a
token, the gap (in the float32 reference) of the token the fp8 reference
puts first; ``harness.run_seed(control=True)`` puts those choices in the
served tokens' place. The faults are a served token altered where the step
produces it, and a step that returns the KV pool as it came in. All must
read ``correct`` false against the limit that sound runs stay under.

Readings at this size (3 seeds each, CPU): sound runs 0.015-0.029, the fp8
control 0.18-0.23; the limit here, 0.08, lies between. The chip's readings
at the cells' own sizes, and their limits, are in PERF.md.
"""
import time

import jax
import jax.numpy as jnp

import harness
import tiny

LIMIT = 0.08
SMALL = dict(hidden_size=256, intermediate_size=512, num_attention_heads=4,
             num_key_value_heads=2, vocab_size=2048, num_hidden_layers=2,
             correct={"max_gap": LIMIT})
MIX = dict(tiny.OPEN, rate_per_s=6.0,
           output_len={"median": 16, "sigma": 0.3, "min": 8, "max": 24})


def test_fp8_control_reads_above_the_limit_and_the_program_below():
    """The harness's control mode: the fp8 reference's first choices stand
    in the served tokens' place and the run reads ``correct`` false against
    the cell's own limit, while the served tokens read under it."""
    cell = tiny.cell(MIX, **SMALL)
    server = harness.Server(cell, on_tpu=False)
    result, lines, run = harness.run_seed(server, 7, 1.5, metrics=[],
                                          control=True)
    got = run.check
    assert got["tokens"] > 40             # the sample of 4 that a run compares
    assert got["max_gap"] <= LIMIT < got["control_gap"], got
    assert result["correct"] is False, (result, lines)
    assert result["compared"]["max_gap"] == {"value": got["control_gap"],
                                             "limit": LIMIT}


def test_a_token_altered_where_it_is_produced_is_not_correct():
    calls = [0]

    def hook(engine):
        inner = engine._step_call
        vocab = engine.cfg.vocab_size

        def faulty(*args):
            next_tok, cache = inner(*args)
            calls[0] += 1
            if calls[0] % 5 == 0:             # every fifth step, every row
                next_tok = (next_tok + 1) % vocab
            return next_tok, cache
        engine._step_call = faulty

    cell = tiny.cell(MIX, **SMALL)
    result, lines = harness.run_once(cell, 11, 1.5,
                                     process_start=time.perf_counter(),
                                     on_tpu=False, metrics=[],
                                     engine_hook=hook)
    assert calls[0] > 10
    assert result["correct"] is False, (result, lines)
    assert result["compared"]["max_gap"]["value"] > LIMIT


def test_a_step_that_leaves_the_kv_pool_unchanged_is_not_correct():
    def hook(engine):
        inner = engine._step_call

        def faulty(cache, *rest):             # the pool as it came in
            kept = jax.tree.map(jnp.copy, cache)
            next_tok, _ = inner(cache, *rest)
            return next_tok, kept
        engine._step_call = faulty

    cell = tiny.cell(MIX, **SMALL)
    result, lines = harness.run_once(cell, 13, 1.5,
                                     process_start=time.perf_counter(),
                                     on_tpu=False, metrics=[],
                                     engine_hook=hook)
    assert result["correct"] is False, (result, lines)
    assert result["compared"]["max_gap"]["value"] > LIMIT
