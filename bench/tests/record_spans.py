"""Record the small chip trace that ``test_trace_spans.py`` reads.

  python bench/tests/record_spans.py --workload phi3-mini-4k.decode-long \
      --out bench/tests/data

Runs on the chip, as ``record_trace.py`` does: sets the cell up, serves a
few of its requests into decode, then traces ``--ticks`` ticks, with the
engine's own spans inside each ``bench.tick``. Writes
``trace_spans.xplane.pb`` and ``trace_spans.json`` (the Mosaic kernels'
names, each traced tick's rows and the compiled step's scope map,
``trace_spans.scope_map``) to ``--out``; compress the trace with ``xz``.
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
    import trace_spans
    from repro.engine import Request

    harness.enable_compile_cache()
    cell = spec.load_cell(args.workload)
    server = harness.Server(cell)
    scopes = trace_spans.scope_map(
        server.engine.lower_step().compile().as_text())
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
        tmp = os.path.join(spec.ROOT, "bench_out", "record_spans")
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
    dst = os.path.join(args.out, "trace_spans.xplane.pb")
    shutil.copy(trace_reduce.find(tmp), dst)
    with open(os.path.join(args.out, "trace_spans.json"), "w") as f:
        json.dump({"workload": args.workload, "kernel_ops": server.kernel_ops,
                   "rows": rows, "device_kind": jax.devices()[0].device_kind,
                   "scope_map": scopes}, f)
    shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps({"ok": True, "bytes": os.path.getsize(dst)}))


if __name__ == "__main__":
    main()
