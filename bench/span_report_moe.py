"""``span_report.py`` with the expert layer's scopes attributed and its
counters read.

  python bench/span_report_moe.py --workload deepseek-v2-lite-ep4.decode-4k \
      --seed 7 --seconds 50

The same traced run and reading as ``span_report.py``, with the scopes of
an expert-parallel share's layer (``models/blocks.py``: ``moe`` and inside
it ``moe.router``, ``moe.experts``, ``moe.shared``) added to the named
scopes ``trace_spans.scope_map`` charges device time to; without them that
time is unscoped. A second JSON line gives the engine's expert counters
(``Engine.metrics()``: ``moe_pairs``, ``moe_pairs_here``,
``moe_expert_pairs_max``) over the traced part of the window and over the
whole run (warm-up and set-up included).
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))

MOE_SCOPES = ("moe", "moe.router", "moe.experts", "moe.shared")


def main() -> None:
    import harness
    import span_report
    import trace_spans
    trace_spans.SCOPES = trace_spans.SCOPES + MOE_SCOPES
    span_report.PROCESS_START = PROCESS_START

    servers, snaps = [], []

    class Server(harness.Server):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            servers.append(self)

    switch = harness.Server._trace_switch

    def trace_switch(trace_dir, tracing, now, at, length):
        on = switch(trace_dir, tracing, now, at, length)
        if on != tracing:                # the trace started or stopped
            snaps.append(dict(servers[-1].engine.moe_counts))
        return on

    harness.Server = Server
    Server._trace_switch = staticmethod(trace_switch)
    span_report.main()
    total = servers[-1].engine.moe_counts
    window = ({k: snaps[1][k] - snaps[0][k] for k in total}
              if len(snaps) == 2 else None)
    print(json.dumps({"moe_counters": {"traced": window, "run": total}}),
          flush=True)


if __name__ == "__main__":
    main()
