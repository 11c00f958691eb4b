"""The metric readers' arithmetic on synthetic timelines."""
import numpy as np
import pytest

import harness
import tiny
import trace_reduce as tr
from traffic import Planned


class Req:
    def __init__(self, out, done=True):
        self.out_tokens, self.done = out, done


def record(rid, sent, times, prompt=10, admitted=None, done=True):
    p = Planned(rid, np.zeros(prompt, np.int32), max_new=len(times))
    r = harness.ReqRecord(p, sent=sent, req=Req([0] * len(times), done),
                          admitted=admitted, token_times=list(times))
    return r


def make_run(requests, ticks, seconds=10.0):
    run = harness.Run(tiny.cell(), seconds, setup_s=3.5, window_s=seconds)
    run.requests, run.ticks = requests, ticks
    return run


def read(name, run):
    return harness.load_reader(name)(run)


def test_latency_tails_and_slo():
    reqs = [record(i, sent=float(i), times=[i + 0.1 * (i + 1), i + 0.1 * (i + 1) + 0.02,
                                               i + 0.1 * (i + 1) + 0.05],
                   admitted=i + 0.01 * i) for i in range(10)]
    reqs.append(record(10, sent=11.0, times=[11.5]))      # not due
    run = make_run(reqs, [harness.Tick(0, 20.5, 1)])
    ttfts = [0.1 * (i + 1) for i in range(10)]
    assert read("ttft_p95_ms", run) == pytest.approx(np.percentile(ttfts, 95) * 1e3)
    gaps = [0.02, 0.03] * 10
    assert read("itl_p95_ms", run) == pytest.approx(np.percentile(gaps, 95) * 1e3)
    waits = [0.01 * i for i in range(10)]
    assert read("queue_wait_p95_ms", run) == pytest.approx(
        np.percentile(waits, 95) * 1e3)
    # tiny limits: ttft 5000 + 100 * 10 ms, mean gap 5000 ms: all met
    assert read("slo_pct", run) == 100.0
    run.requests[0].req.done = False                         # unfinished misses
    assert read("slo_pct", run) == 90.0
    assert read("setup_s", run) == 3.5


def test_a_request_never_served_counts_its_wait():
    reqs = [record(0, sent=1.0, times=[1.2]), record(1, sent=2.0, times=[], done=False)]
    run = make_run(reqs, [harness.Tick(0.0, 9.0, 1)])
    assert read("ttft_p95_ms", run) == pytest.approx(
        np.percentile([0.2, 7.0], 95) * 1e3)


def test_throughput_and_rows():
    ticks = [harness.Tick(i * 0.5, i * 0.5 + 0.4, rows=r, emitted=e)
             for i, (r, e) in enumerate([(4, 2), (4, 4), (2, 2)])]
    run = make_run([], ticks, seconds=2.0)
    assert read("out_tok_per_s", run) == pytest.approx(8 / 2.0)
    assert read("rows_per_tick", run) == pytest.approx(10 / 3)
    run.preemptions = 3
    assert read("preemptions", run) == 3


def test_trace_readers():
    ticks = [harness.Tick(0, 1, 2, work=[(100, 1), (0, 8)], emitted=1)
             for _ in range(4)]
    run = make_run([], ticks)
    assert read("device_idle_pct", run) is None           # no trace: nothing
    run.trace = {"ticks": [1, 2], "window_s": 0.5, "busy_s": 0.4,
                 "kernel_s": 0.01, "step_s": [0.2, 0.2],
                 "tick_host_s": [0.05, 0.03]}
    run.peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert read("device_idle_pct", run) == pytest.approx(20.0)
    assert read("step_device_ms", run) == pytest.approx(200.0)
    assert read("tick_host_ms", run) == pytest.approx(40.0)
    import work
    g = run.geometry
    least = 2 * g.layers * work.kernel_min_seconds(g, ticks[0].work, run.peak)
    assert read("paged_attn_roofline", run) == pytest.approx(100 * least / 0.01)
    flops = 2 * work.step_flops(g, ticks[0].work, 1,
                                run.cell.block().layer_matmul_params(g))
    assert read("mfu_pct", run) == pytest.approx(100 * flops / (0.5 * 197e12))
    run.trace["kernel_s"] = 0.0                            # never a 0% share
    assert read("paged_attn_roofline", run) is None


def span(s, e, name="op", **stats):
    return tr.Span(float(s), float(e), name, stats)


def test_reduce_on_a_synthetic_timeline():
    host = [span(0, 100, "bench.tick", tick=0), span(100, 110, "bench.record"),
            span(120, 200, "bench.tick", tick=1)]
    ops = {0: [span(10, 40, "fusion.1"), span(30, 60, "custom-call.2"),
               span(130, 190, "custom-call.2"), span(250, 260, "late")]}
    mods = {0: [span(10, 60, "jit_paged_step(1)"), span(130, 190, "jit_paged_step(1)")]}
    out = tr.reduce(tr.Trace(ops, mods, host), ["custom-call.2"], "jit_paged_step")
    assert out["window_s"] == pytest.approx(200e-9)
    assert out["busy_s"] == pytest.approx(110e-9)           # 10-60 and 130-190
    assert out["kernel_s"] == pytest.approx(90e-9)
    assert out["step_s"] == pytest.approx([50e-9, 60e-9])
    assert out["tick_host_s"] == pytest.approx([50e-9, 20e-9])
    assert out["tick_ids"] == [0, 1]
    gaps = out["breakdown"]["idle_gaps"]
    assert gaps[0][0] == "bench.tick" and gaps[0][1] == pytest.approx(70e-9)
    assert [g[1] for g in gaps] == pytest.approx([70e-9, 10e-9, 10e-9])
    assert tr.host_span_at(host, 105) == "bench.record"
    assert tr.host_span_at(host, 115).startswith("host")
    assert out["breakdown"]["device_ops"][0] == ["custom-call.2", pytest.approx(90e-9)]
    assert tr.op_kind("%fusion.860.remat15 = bf16[1] fusion(x), kind=kLoop") == \
        "fusion.remat[kLoop]"
    assert tr.short_name("%paged_attention.23 = bf16[2] custom-call(a)") == \
        "paged_attention.23"


def test_union_and_gaps():
    u = tr.union([(5, 10), (0, 3), (2, 6), (20, 30)], 1, 25)
    assert u == [(1, 10), (20, 25)]
    assert tr.covered(u, 0, 30) == 14
    assert tr.gaps(u, 0, 30) == [(0, 1), (10, 20), (25, 30)]


def test_every_benchmark_metric_has_a_reader_and_splits_read_alike():
    import os
    import spec
    bench = spec.read_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    ticks = [harness.Tick(0, 1, 2, work=[(100, 1), (0, 8)], emitted=1)
             for _ in range(4)]
    reqs = [record(i, sent=float(i), times=[i + 0.2, i + 0.25, i + 0.4])
            for i in range(5)]
    run = make_run(reqs, ticks)
    run.trace = {"ticks": [1, 2], "window_s": 0.5, "busy_s": 0.4,
                 "kernel_s": 0.01, "step_s": [0.2, 0.2],
                 "tick_host_s": [0.05, 0.03]}
    run.peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    for m in bench["end_to_end"] + bench["per_layer"]:
        base = m["name"].split(".")[0]
        assert read(m["name"], run) == read(base, run), m["name"]
