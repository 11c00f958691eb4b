"""Benchmark harness entry point — one bench module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only substr]

Prints ``name,us_per_call,derived`` CSV (stdout), one row per measurement.
Every module also writes its own standardized ``BENCH_<name>.json`` at the
repo root (benchmarks/common.py schema), and this harness writes an
aggregate ``BENCH_run.json`` over everything it ran.

Paper figure -> module map (DESIGN.md §7):

  Fig 5/6   bench_mailbox_overhead    AM put vs raw put, without-execution
  Fig 7/8   bench_injected_vs_local   code-in-message vs resident function
  Fig 9/10  bench_stashing            VMEM-fused vs HBM-roundtrip execution
  Fig 11/12 bench_tail_latency        p50/p99.9/tail-spread under load
  Fig 13/14 bench_wfe                 semaphore wait vs spin-poll cycles
  §Roofline bench_roofline            3-term roofline per dry-run cell
  §VII-B    bench_paged_attention     stash-resident kernel occupancy sweep
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time
import traceback

from benchmarks import (bench_graph, bench_injected_vs_local,
                        bench_mailbox_overhead, bench_paged_attention,
                        bench_roofline, bench_serving, bench_stashing,
                        bench_tail_latency, bench_wfe)
from benchmarks.common import write_bench_json
from repro.launch.compile_cache import enable_compile_cache

MODULES = (
    ("fig5_6", bench_mailbox_overhead),
    ("fig7_8", bench_injected_vs_local),
    ("fig9_10", bench_stashing),
    ("fig11_12", bench_tail_latency),
    ("fig13_14", bench_wfe),
    ("roofline", bench_roofline),
    ("serving", bench_serving),
    ("paged_attention", bench_paged_attention),
    ("graph", bench_graph),
)


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--only", default=None,
                   help="run only modules whose tag contains this substring")
    args = p.parse_args()
    enable_compile_cache()

    print("name,us_per_call,derived")
    failed = []
    by_module = {}
    for tag, mod in MODULES:
        if args.only and args.only not in tag:
            continue
        t0 = time.time()
        try:
            rows = mod.main()
            by_module[tag] = [dataclasses.asdict(r) for r in rows]
            for row in rows:
                print(row.csv(), flush=True)
        except Exception as e:  # noqa: BLE001 - report, keep harness going
            failed.append(tag)
            print(f"{tag},0.00,ERROR {type(e).__name__}: {e}", flush=True)
            traceback.print_exc(file=sys.stderr)
        print(f"# {tag} done in {time.time()-t0:.1f}s", flush=True)
    write_bench_json("run", config={"only": args.only},
                     extra_metrics={"modules": by_module, "failed": failed})
    if failed:
        raise SystemExit(f"benchmark modules failed: {failed}")


if __name__ == "__main__":
    main()
