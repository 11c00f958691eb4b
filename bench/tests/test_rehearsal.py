"""bench/run.py's path end to end on the CPU, at a tiny size."""
import json
import os
import subprocess
import sys
import time

import pytest

import harness
import spec
import tiny

E2E = [{"name": n, "unit": u} for n, u in (
    ("ttft_p95_ms", "ms"), ("itl_p95_ms", "ms"), ("out_tok_per_s", "tokens/s"),
    ("slo_pct", "%"), ("setup_s", "s"))]
LAYER = [{"name": n, "unit": u} for n, u in (
    ("queue_wait_p95_ms", "ms"), ("rows_per_tick", "rows"),
    ("preemptions", "count"))]


@pytest.mark.parametrize("traffic", [tiny.OPEN, tiny.CLOSED],
                         ids=["open", "closed"])
def test_tiny_cell_runs_and_is_correct(traffic):
    cell = tiny.cell(traffic)
    result, lines = harness.run_once(cell, seed=2 ** 40 + 3, seconds=3.0,
                                     process_start=time.perf_counter(),
                                     on_tpu=False, metrics=E2E + LAYER)
    assert result["correct"], (result, lines)
    assert result["attempted"] > 0 and result["failed"] == 0
    m = result["metrics"]
    assert set(m) == {x["name"] for x in E2E + LAYER}
    assert m["out_tok_per_s"]["value"] > 0 and m["setup_s"]["value"] > 0
    assert list(result)[-1] == "compared"
    assert lines[-1].startswith("compared short_outputs")
    json.dumps(result)


def test_traced_tick_work_matches_the_step_inputs():
    """The rows a traced tick records (from the requests' resident counts)
    are the rows the step was given (its starts and n_valid)."""
    fed, recorded = [], []

    def hook(engine):
        inner_step, inner_tick = engine._step_call, engine.tick

        def step(*args):
            import numpy as np
            starts, n_valid = (np.asarray(a) for a in args[-2:])
            fed.append(sorted((int(s), int(n)) for s, n in zip(starts, n_valid)
                              if n > 0))
            return inner_step(*args)

        def tick():
            before = [(e, e.pos) for e in engine.slot_entry if e is not None]
            n = inner_tick()
            rows = sorted(harness.step_rows(before, engine.slot_entry))
            if rows:
                recorded.append(rows)
            return n
        engine._step_call, engine.tick = step, tick

    cell = tiny.cell(dict(tiny.CLOSED, clients=5, slots=5),
                     serving=dict(tiny.CONFIG["serving"], pool_bytes=7 * 2048))
    server = harness.Server(cell, on_tpu=False, engine_hook=hook)
    server.load(5)
    planned = harness.traffic_lib.generate(cell.traffic, 5, 6.0,
                                           cell.geometry.vocab)
    server.warm_up(planned)
    fed.clear()
    recorded.clear()
    preempted = server.engine.preempt_count
    run = harness.Run(cell, 6.0, setup_s=0.0)
    server.measure(planned, 6.0, run, trace_dir=None)
    assert len(fed) > 5 and recorded == fed
    # the small pool forces some while the rows are compared (in the
    # window, or while the clients' first prompts fill before it opens)
    assert server.engine.preempt_count > preempted


def test_run_py_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH, "run.py"), "--workload",
         "mistral-7b-v03-pp2.chat", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=spec.ROOT, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
