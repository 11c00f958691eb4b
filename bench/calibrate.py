"""Readings that set a cell's correctness limit, on the chip.

  python bench/calibrate.py --workload phi3-mini-4k.decode-long \
      --seeds 101,102,103 --seconds 20 [--control]

One process, one set-up: for each seed, ``harness.run_seed`` makes that
seed's weights and traffic, serves a short window at the cell's own load
(an open loop's requests followed to the end), frees the program's state
and judges a sample of the finished requests against the float32
reference, as a run does. Without ``--control`` the served tokens are
judged; with it the fp8 control stands in their place and ``correct``
must read false against the cell's own limit. Either way the line gives
both readings: ``max_gap`` of the served tokens and, with ``--control``,
``control_gap``. One JSON line per seed. The largest ``max_gap`` of sound
runs is the limit's lower reading, the smallest ``control_gap`` its upper
one (PERF.md).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--control", action="store_true")
    args = p.parse_args()

    import harness
    import spec

    harness.enable_compile_cache()
    cell = spec.load_cell(args.workload)
    server = harness.Server(cell)
    counter = harness.CompileCounter()
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        if i:
            server.engine.restart()      # drop the last seed's requests
        result, _, run = harness.run_seed(server, seed, args.seconds,
                                          counter=counter, metrics=[],
                                          control=args.control, warm=i == 0)
        print(json.dumps({"seed": seed, "control": args.control,
                          "correct": result["correct"],
                          "compared": result["compared"], **run.check,
                          "due": result["attempted"],
                          "failed": result["failed"],
                          "preemptions": run.preemptions,
                          "compiles_in_window": run.compiles,
                          "pool_peak_blocks": run.pool_peak_blocks,
                          "pool_blocks": run.pool_blocks,
                          "seconds": round(time.perf_counter() - t, 1)}),
              flush=True)


if __name__ == "__main__":
    main()
