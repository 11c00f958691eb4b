"""The trace reduction on a trace recorded on a TPU v5e chip.

``data/trace.xplane.pb.xz`` holds three decode ticks of the Mistral cell's
engine, four requests at positions 43-45 (``record_trace.py``);
``data/trace.json`` holds the step's Mosaic kernel names and each tick's
rows as the harness recorded them.
"""
import json
import os

import pytest

import trace_reduce as tr
import work
from spec import load_cell

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "trace.json")) as f:
        meta = json.load(f)
    trace = tr.load(os.path.join(DATA, "trace.xplane.pb.xz"))
    return meta, trace, tr.reduce(trace, meta["kernel_ops"], "jit_paged_step")


def test_the_window_holds_the_three_ticks(recorded):
    meta, trace, out = recorded
    assert out["tick_ids"] == [0, 1, 2]
    assert 0 < out["busy_s"] <= out["window_s"]
    assert len(out["step_s"]) == 3 and len(out["tick_host_s"]) == 3


def test_device_runs_lie_inside_their_ticks(recorded):
    """Host spans and device events share one clock: each run of the step
    program starts and ends inside a bench.tick span."""
    _, trace, _ = recorded
    ticks = [s for s in trace.host if s.name == "bench.tick"]
    runs = [s for s in trace.modules[0] if s.name.startswith("jit_paged_step")]
    for t in ticks:
        inside = [r for r in runs if t.start <= r.start and r.end <= t.end]
        assert len(inside) == 1


def test_every_layer_runs_the_kernel_once_a_tick(recorded):
    meta, _, out = recorded
    layers = load_cell(meta["workload"]).geometry.layers
    assert out["kernel_calls"] == 3 * layers
    assert 0 < out["kernel_s"] < sum(out["step_s"])


def test_shares_stay_under_100_percent(recorded):
    meta, _, out = recorded
    cell = load_cell(meta["workload"])
    g = cell.geometry
    per_layer = cell.block().layer_matmul_params(g)
    peak = work.peaks(meta["device_kind"])
    least = sum(g.layers * work.kernel_min_seconds(g, rows, peak)
                for rows in meta["rows"])
    assert 0 < least / out["kernel_s"] < 1
    flops = sum(work.step_flops(g, rows, len(rows), per_layer) for rows in meta["rows"])
    assert 0 < flops / (out["window_s"] * peak["bf16_flops_per_s"]) < 1


def test_breakdown_names_kinds_and_host_spans(recorded):
    _, _, out = recorded
    ops = out["breakdown"]["device_ops"]
    assert 0 < len(ops) <= 10 and ops == sorted(ops, key=lambda kv: -kv[1])
    assert "paged_attention" in [k for k, _ in ops]
    assert all(" = " not in k for k, _ in ops)
    gaps = out["breakdown"]["idle_gaps"]
    assert 0 < len(gaps) <= 10
    assert all(name.startswith(("bench.", "host")) for name, _ in gaps)
