"""Find a mix's knee on the chip, once, when a cell is defined.

  python bench/sweep.py --workload mistral-7b-v03-pp2.chat \
      --rates 0.85,1.0,1.15 --seconds 40 --seed 21 [--unloaded]

One process, one set-up. ``--unloaded`` first serves requests one at a
time: four prompts across the mix's range with a short answer each, then
one long answer. It reads the time to first token against the prompt's
length and the gap between tokens of a lone request, from which the mix's
latency limits are set. Then, for each rate, an open-loop window of
``--seconds`` at that rate, its requests followed to the end: the share
that met the limits (``slo_pct``), the tails, and the queue's length at
the middle and the end of the window (a backlog that grows means the rate
is past the knee). One JSON line per phase.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
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
    p.add_argument("--rates", default="")
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seed", type=int, default=5)
    p.add_argument("--unloaded", action="store_true")
    args = p.parse_args()

    import numpy as np

    import harness
    import spec
    import traffic as traffic_lib
    from repro.engine import Request

    harness.enable_compile_cache()
    cell = spec.load_cell(args.workload)
    server = harness.Server(cell)
    server.load(args.seed)
    server.warm_up(traffic_lib.generate(cell.traffic, args.seed, 1.0,
                                        cell.geometry.vocab))
    eng = server.engine

    if args.unloaded:
        lo, hi = cell.traffic["prompt_len"]["min"], cell.traffic["prompt_len"]["max"]
        lens = [lo, (lo + hi) // 4, (lo + hi) // 2, hi]
        asks = [(n, 4) for n in lens] + [(lo, min(64, cell.traffic["output_len"]["max"]))]
        rng = np.random.default_rng(args.seed)
        ttft, gaps = [], []
        with server.mesh:
            for i, (L, new) in enumerate(asks):
                req = Request(10_000 + i, rng.integers(0, cell.geometry.vocab, L,
                                                       dtype=np.int32),
                              max_new_tokens=new)
                t0 = time.perf_counter()
                eng.submit(req)
                times = []
                while not req.done:
                    eng.tick()
                    while len(times) < len(req.out_tokens):
                        times.append(time.perf_counter() - t0)
                if i < len(lens):
                    ttft.append((L, times[0] * 1e3))
                else:
                    gaps = np.diff(times) * 1e3
        slope, base = np.polyfit([x for x, _ in ttft], [y for _, y in ttft], 1)
        print(json.dumps({"phase": "unloaded", "ttft_ms": ttft,
                          "ttft_ms_base": base, "ttft_ms_per_prompt_token": slope,
                          "itl_ms_mean": float(np.mean(gaps)),
                          "itl_ms_p95": float(np.percentile(gaps, 95))}),
              flush=True)

    read = {n: harness.load_reader(n) for n in
            ("ttft_p95_ms", "itl_p95_ms", "slo_pct", "queue_wait_p95_ms",
             "rows_per_tick", "out_tok_per_s")}
    tick = eng.tick
    for rate in (float(r) for r in args.rates.split(",") if r):
        mix = dict(cell.traffic, rate_per_s=rate)
        server.cell = dataclasses.replace(cell, traffic=mix)
        run = harness.Run(server.cell, args.seconds, setup_s=0.0)
        queued = []                      # the queue's length as each tick began

        def probed():
            queued.append(len(eng.queue))
            return tick()
        eng.tick = probed
        server.measure(traffic_lib.generate(mix, args.seed, args.seconds,
                                            cell.geometry.vocab),
                       args.seconds, run)
        del eng.tick
        eng.cache = None                 # restart() makes the pool again
        gc.collect()
        eng.restart()
        at = lambda t: ([q for q, tk in zip(queued, run.ticks)
                         if tk.start < t] or [0])[-1]
        out = {"phase": "rate", "rate_per_s": rate, "due": len(run.due()),
               "finished": sum(r.done for r in run.due()),
               "queued_mid": at(args.seconds / 2), "queued_end": at(args.seconds),
               "ticks": len(run.ticks), "window_s": run.window_s}
        out.update({k: f(run) for k, f in read.items()})
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
