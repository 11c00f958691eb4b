"""Record the small chip trace that ``test_trace_reduce.py`` reads.

  python bench/tests/record_trace.py --workload mistral-7b-v03-pp2.chat \
      --out bench/tests/data

Runs on the chip: sets the cell up, serves a few of its requests into
decode, then traces ``--ticks`` ticks and writes ``trace.xplane.pb`` and
``trace.json`` (the Mosaic kernels' names and each traced tick's rows) to
``--out``.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--out", required=True)
    p.add_argument("--ticks", type=int, default=3)
    p.add_argument("--seed", type=int, default=1)
    args = p.parse_args()

    import jax
    import harness
    import spec
    import trace_reduce
    from repro.engine import Request

    harness.enable_compile_cache()
    cell = spec.load_cell(args.workload)
    server = harness.Server(cell)
    server.load(args.seed)
    planned = harness.traffic_lib.generate(cell.traffic, args.seed, 10.0,
                                           cell.geometry.vocab)
    server.warm_up(planned)
    eng = server.engine
    with server.mesh:
        for q in planned[:4]:
            eng.submit(Request(q.rid, q.prompt[:40], max_new_tokens=64))
        for _ in range(8):
            eng.tick()
        tmp = os.path.join(spec.ROOT, "bench_out", "record_trace")
        shutil.rmtree(tmp, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        rows = []
        jax.profiler.start_trace(tmp, profiler_options=opts)
        for i in range(args.ticks):
            before = [(e, e.pos) for e in eng.slot_entry if e is not None]
            with jax.profiler.TraceAnnotation("bench.tick", tick=i):
                eng.tick()
            rows.append(harness.step_rows(before, eng.slot_entry))
            time.sleep(0.002)
        jax.profiler.stop_trace()
    os.makedirs(args.out, exist_ok=True)
    shutil.copy(trace_reduce.find(tmp), os.path.join(args.out,
                                                     "trace.xplane.pb"))
    with open(os.path.join(args.out, "trace.json"), "w") as f:
        json.dump({"workload": args.workload, "kernel_ops": server.kernel_ops,
                   "rows": rows, "device_kind": jax.devices()[0].device_kind},
                  f, indent=1)
    shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps({"ok": True, "bytes": os.path.getsize(
        os.path.join(args.out, "trace.xplane.pb"))}))


if __name__ == "__main__":
    main()
