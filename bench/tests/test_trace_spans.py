"""The engine's spans and the step's scopes: ``trace_spans`` on synthetic
timelines and HLO text, on a trace recorded on a TPU v5e chip, and the
reader of the engine's admission stamp.

``data/trace_spans.xplane.pb.xz`` holds three decode ticks of the Phi-3
cell's engine, four requests (``record_spans.py``, which also wrote
``data/trace_spans.json``: the kernel names, each tick's rows and the
step's scope map as the chip compiled it).
"""
import json
import os

import numpy as np
import pytest

import harness
import trace_spans as ts
from test_metrics import make_run, read, record, span

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PHASES = ["engine.admit", "engine.schedule", "engine.inputs",
          "engine.dispatch", "engine.fetch", "engine.emit"]


def test_reduce_splits_the_tick_into_host_phases_and_device_scopes():
    """engine.* spans inside bench.tick: self times per tick, the host
    time before the step's launch, idle time charged to the innermost
    span, and the step's device time by scope."""
    host = [span(0, 100, "bench.tick", tick=0), span(2, 98, "engine.tick"),
            span(4, 10, "engine.admit"), span(10, 20, "engine.schedule"),
            span(12, 16, "engine.preempt"), span(20, 25, "engine.inputs"),
            span(25, 30, "engine.dispatch"), span(30, 90, "engine.fetch"),
            span(90, 96, "engine.emit"), span(100, 110, "bench.record"),
            span(110, 200, "bench.tick", tick=1), span(112, 198, "engine.tick"),
            span(112, 140, "engine.inputs"), span(140, 150, "engine.dispatch"),
            span(150, 190, "engine.fetch")]
    ops = {0: [span(28, 40, "slice.1"), span(40, 70, "fusion.2"),
               span(70, 80, "copy.3"), span(145, 185, "fusion.2"),
               span(185, 189, "param_copy.4")]}
    mods = {0: [span(28, 80, "jit_paged_step(1)"),
                span(145, 189, "jit_paged_step(1)")]}
    scopes = {"slice.1": "kv_pool", "fusion.2": "mlp", "copy.3": "kv_pool"}
    trace = ts.Trace(ops, mods, host)
    out = ts.reduce(trace, "jit_paged_step", scopes)
    assert out["ticks"] == 2
    assert out["step_s"] == pytest.approx([52e-9, 44e-9])
    per_tick = {k: v * 2 / 1e-9 for k, v in out["span_s"].items()}
    assert per_tick["engine.schedule"] == pytest.approx(6)   # 10 minus preempt 4
    assert per_tick["engine.inputs"] == pytest.approx(5 + 28)
    assert per_tick["engine.tick"] == pytest.approx(4 + 8)
    assert per_tick["bench.tick"] == pytest.approx(4 + 4)
    assert out["tick_prep_s"] == pytest.approx([28e-9, 38e-9])
    assert out["scope_s"] == pytest.approx({"kv_pool": 22e-9, "mlp": 70e-9})
    assert out["unscoped_s"] == pytest.approx(4e-9)
    idle = {k: v / 1e-9 for k, v in out["idle_by_span"].items()}
    assert idle == pytest.approx({
        "bench.tick": 2 + 2 + 2 + 2, "engine.tick": 2 + 2 + 8, "engine.admit": 6,
        "engine.schedule": 6, "engine.preempt": 4, "engine.inputs": 5 + 28,
        "engine.dispatch": 3 + 5, "engine.fetch": 10 + 1, "engine.emit": 6,
        "bench.record": 10})
    assert sum(idle.values()) * 1e-9 == pytest.approx(
        out["window_s"] - out["busy_s"])
    # the longest gap, 80-145, is named by the span holding most of it
    assert out["idle_gaps"][0] == ["engine.inputs", pytest.approx(65e-9)]
    assert ts.innermost(host[9:11], 95, 200) == [
        (95, 100, ts.OUTSIDE), (100, 110, "bench.record"),
        (110, 200, "bench.tick")]
    plain = ts.reduce(trace, "jit_paged_step")
    assert plain["scope_s"] == {} and plain["unscoped_s"] == 0.0
    assert ts.reduce(ts.Trace(ops, mods, host[9:10]), "jit_paged_step") is None


HLO = """HloModule jit_paged_step

%fused_computation.1 (p: bf16[4]) -> bf16[4] {
  %p = bf16[4]{0} parameter(0)
  ROOT %n = bf16[4]{0} negate(%p), metadata={op_name="jit(paged_step)/mlp/neg"}
}

ENTRY %main.9 (cache.1: bf16[2,4], x.2: bf16[4]) -> (bf16[4], bf16[2,4]) {
  %cache.1 = bf16[2,4]{1,0} parameter(0), metadata={op_name="cache"}
  %x.2 = bf16[4]{0} parameter(1), metadata={op_name="x"}
  %slice.3 = bf16[1,4]{1,0} slice(%cache.1), slice={[0:1], [0:4]}, metadata={op_name="jit(paged_step)/attention/kv_pool/slice"}
  %fusion.4 = bf16[4]{0:T(128)} fusion(%x.2), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(paged_step)/attention/jit(paged_attention)/pallas_call"}
  %fusion.4.remat_compressed = bf16[4]{0} copy(%fusion.4)
  %copy.5 = bf16[1,4]{1,0} copy(%bitcast.6)
  %bitcast.6 = bf16[1,4]{1,0} bitcast(%slice.3)
  %copy-start.7 = (bf16[4]{0}, bf16[4]{0}, u32[]) copy-start(%x.2)
  %copy-done.7 = bf16[4]{0} copy-done(%copy-start.7)
  %get-tuple-element.8 = bf16[4]{0} get-tuple-element(%tuple.9), index=0
  %tuple.9 = (bf16[4]{0}, bf16[4]{0}) tuple(%fusion.4.remat_compressed, %copy-done.7)
  ROOT %tuple.10 = (bf16[4]{0}, bf16[2,4]{1,0}) tuple(%fusion.4, %cache.1), metadata={op_name="jit(paged_step)/head/tuple"}
}
"""


def test_scope_map_takes_the_innermost_scope_and_follows_operands():
    m = ts.scope_map(HLO)
    assert m["slice.3"] == "kv_pool"                 # innermost of two scopes
    assert m["fusion.4"] == "attention"
    assert m["fusion.4.remat_compressed"] == "attention"   # no metadata: operand's
    assert m["copy.5"] == "kv_pool"                  # through a bitcast
    assert m["tuple.10"] == "head"
    assert m["get-tuple-element.8"] == "attention"   # first operand, a tuple
    for name in ("cache.1", "x.2", "copy-start.7", "copy-done.7", "n", "p"):
        assert name not in m, name                   # unscoped, or not ENTRY's
    assert ts.scope_map("HloModule empty") == {}


class Stamped:
    """A request as the engine stamps it (``time.perf_counter`` seconds)."""

    def __init__(self, submitted_at, admitted_at):
        self.out_tokens, self.done = [0], True
        self.submitted_at, self.admitted_at = submitted_at, admitted_at


def test_admit_wait_reads_the_engine_stamps():
    """Arrival to the engine's admission: the harness's lateness plus the
    engine's own submitted-to-admitted wait, on a clock whose origin is
    not the window's."""
    reqs = [record(i, sent=float(i), times=[i + 0.5]) for i in range(10)]
    run = make_run(reqs, [harness.Tick(0.0, 9.0, 2)])
    assert read("admit_wait_p95_ms", run) is None      # a program without stamps
    origin = 1000.0
    for i, r in enumerate(reqs):
        r.submitted = r.sent + 0.001 * i               # the harness's lateness
        r.req = Stamped(origin + r.submitted, origin + r.submitted + 0.01 * i)
    reqs[9].req.admitted_at = None                     # never admitted: waits to 9.0
    waits = [0.011 * i for i in range(9)] + [0.0]
    assert read("admit_wait_p95_ms", run) == pytest.approx(
        np.percentile(waits, 95) * 1e3)
    reqs[8].req = None                                 # refused: waits to 9.0 too
    waits[8] = 1.0
    assert read("admit_wait_p95_ms", run) == pytest.approx(
        np.percentile(waits, 95) * 1e3)


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "trace_spans.json")) as f:
        meta = json.load(f)
    trace = ts.load(os.path.join(DATA, "trace_spans.xplane.pb.xz"))
    out = ts.reduce(trace, "jit_paged_step", meta["scope_map"])
    return meta, trace, out


def _inside(spans, outer, name=None):
    return [s for s in spans if outer.start <= s.start and s.end <= outer.end
            and s is not outer and (name is None or s.name == name)]


def test_each_tick_nests_its_phases_in_order(recorded):
    """Inside every bench.tick, one engine.tick; inside that, the six
    phase spans, one after another."""
    _, trace, _ = recorded
    ticks = [s for s in trace.host if s.name == "bench.tick"]
    assert len(ticks) == 3
    for t in ticks:
        (eng,) = _inside(trace.host, t, "engine.tick")
        phases = [s for s in _inside(trace.host, eng)
                  if s.name.startswith("engine.")]
        assert [s.name for s in phases] == PHASES
        assert all(a.end <= b.start for a, b in zip(phases, phases[1:]))


# The profiler puts the device's events on the host's clock to within about
# a millisecond: in this recording each run of the step appears 0.7-1.1 ms
# before its dispatch span starts. Runs are 120 ms apart, so the pairing of
# a run with its tick still shows.
ALIGN_NS = 2e6


def test_the_step_runs_between_dispatch_and_fetch(recorded):
    """Host spans and device events share one clock: the step program's
    run starts after its tick's dispatch starts and ends before the
    token fetch ends."""
    _, trace, _ = recorded
    runs = [s for s in trace.modules[0] if s.name.startswith("jit_paged_step")]
    for eng in (s for s in trace.host if s.name == "engine.tick"):
        (dispatch,) = _inside(trace.host, eng, "engine.dispatch")
        (fetch,) = _inside(trace.host, eng, "engine.fetch")
        (run,) = [r for r in runs if eng.start <= r.start <= eng.end]
        assert dispatch.start - ALIGN_NS <= run.start
        assert run.end <= fetch.end


def test_the_scopes_sum_to_the_step(recorded):
    """The five scopes, with what the map leaves unscoped, account for
    the step's device time; the unscoped part is small."""
    _, _, out = recorded
    step = sum(out["step_s"])
    scoped = sum(out["scope_s"].values())
    assert set(out["scope_s"]) <= set(ts.SCOPES)
    assert {"attention", "kv_pool", "mlp", "head"} <= set(out["scope_s"])
    assert scoped + out["unscoped_s"] == pytest.approx(step, rel=0.02)
    assert out["unscoped_s"] <= 0.05 * step


def test_host_phases_and_idle_time_are_named(recorded):
    _, _, out = recorded
    assert len(out["tick_prep_s"]) == 3
    assert all(0 < x < 0.1 for x in out["tick_prep_s"])
    assert set(PHASES) <= set(out["span_s"])
    idle = out["idle_by_span"]
    assert sum(idle.values()) == pytest.approx(
        out["window_s"] - out["busy_s"], rel=1e-6)
    # record_spans.py sleeps between ticks, outside every span
    assert all(name.startswith(("engine.", "bench.", "host"))
               for name in list(idle) + [n for n, _ in out["idle_gaps"]])
    assert any(name.startswith("engine.") for name in idle)
