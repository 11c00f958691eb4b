"""Where a cell's tick goes, by the engine's spans and the step's scopes.

  python bench/span_report.py --workload phi3-mini-4k.decode-long \
      --seed 7 --seconds 50

Runs on the chip: one traced run of the cell, exactly as ``bench/run.py
--trace 1`` makes it (the same set-up, window, check and per-layer
metrics), then reads the same trace with ``trace_spans``: the step's
device time by named scope (``kv_pool``, ``attention``, ``mlp``, ``head``,
``embed``, unscoped), each host phase's self time a tick
(``engine.admit`` ... ``engine.emit``), the host time before each step's
launch, and the device's idle time by the innermost host span. Prints one
JSON object: ``result`` (run.py's result line) and ``spans`` (in ms; the
scopes per step run, the phases per tick).
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))


def summary(out: dict) -> dict:
    """``trace_spans.reduce``'s output in milliseconds: device scopes per
    step run, host phases and idle time per traced tick."""
    runs = max(1, len(out["step_s"]))
    ticks = max(1, out["ticks"])
    ms = lambda v, k: round(1e3 * v / k, 4)
    prep = sorted(out["tick_prep_s"])
    return {
        "steps": len(out["step_s"]), "ticks": out["ticks"],
        "step_ms": ms(sum(out["step_s"]), runs),
        "scope_ms": {k: ms(v, runs) for k, v in out["scope_s"].items()},
        "unscoped_ms": ms(out["unscoped_s"], runs),
        "span_ms": {k: ms(v, 1) for k, v in out["span_s"].items()},
        "idle_ms": {k: ms(v, ticks) for k, v in out["idle_by_span"].items()},
        "tick_prep_ms": ms(sum(prep), max(1, len(prep))),
        "tick_prep_median_ms": ms(prep[len(prep) // 2], 1) if prep else None,
        "idle_gaps": [[k, ms(v, 1)] for k, v in out["idle_gaps"]],
    }


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__,
                                formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args()

    import harness
    import spec
    import trace_reduce
    import trace_spans

    harness.enable_compile_cache()
    cell = spec.load_cell(args.workload)
    server = harness.Server(cell)
    scopes = trace_spans.scope_map(
        server.engine.lower_step().compile().as_text())
    trace_dir = os.path.join(spec.ROOT, "bench_out", "span_report",
                             f"{args.workload}-{args.seed}")
    shutil.rmtree(trace_dir, ignore_errors=True)
    result, lines, _ = harness.run_seed(
        server, args.seed, args.seconds, counter=harness.CompileCounter(),
        process_start=PROCESS_START, trace_dir=trace_dir)
    for line in lines:
        print(f"[span_report] {line}", file=sys.stderr, flush=True)
    out = trace_spans.reduce(
        trace_spans.load(trace_reduce.find(trace_dir)), "jit_paged_step",
        scopes)
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "result": result,
                      "spans": summary(out) if out is not None else None}),
          flush=True)


if __name__ == "__main__":
    main()
