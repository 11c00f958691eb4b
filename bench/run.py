"""Serving benchmark: one run of one cell of ``BENCHMARK.json``.

  python bench/run.py --workload mistral-7b-v03-pp2.chat --seed 7 \
      --seconds 40 --trace 0

Set-up (TPU start, the engine and its step program, weights from the seed,
a warm-up on the cell's own traffic) is ``setup_s``. Then the window runs
for ``--seconds``; an open loop's requests due in it are followed after it
closes. With ``--trace 0`` the result holds the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read from a profiler trace of
part of the window. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and with
a trace ``breakdown``), and last ``compared``, each number the check
compared beside its limit. The run needs a TPU: without one, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
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


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__,
                                formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    import spec
    cell = spec.load_cell(args.workload)
    try:
        import repro  # noqa: F401
    except ImportError as e:
        log(f"the program is not in this checkout ({e})")
        return 2

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        log(f"{args.workload} needs {cell.chips} TPU chip(s); JAX found "
            f"{len(devices)} {devices[0].platform} device(s)")
        return 2

    import harness
    harness.enable_compile_cache()
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(spec.ROOT, "bench_out", "trace",
                                 f"{args.workload}-{args.seed}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    result, lines = harness.run_once(cell, args.seed, args.seconds,
                                     trace_dir=trace_dir,
                                     process_start=PROCESS_START)
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    for line in lines:
        log(line)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
