"""From a profiler trace (``.xplane.pb``) to the per-layer numbers.

``load`` reads the trace with ``jax.profiler.ProfileData`` into three
timelines on the profiler's clock: the device's operations (the ``XLA
Ops`` line of each TPU plane), the device's program runs (``XLA Modules``)
and the harness's host spans (``bench.*``). ``reduce`` then takes the
window from the first traced ``bench.tick`` span's start to the last one's
end, and gives:

  busy_s, window_s   union of the device's operation intervals in the
                     window (averaged over the chips), and its length
  kernel_s           device time of the named kernel's operations
  step_s             device time of each run of the step program
  tick_host_s        each tick span's length minus the device time inside
  breakdown          the kinds of operation that took most time (``op_kind``),
                     and the longest idle gaps, each named by the host span
                     whose time holds the gap's middle
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]            # (start, end) in ns

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


@dataclasses.dataclass
class Span:
    start: float
    end: float
    name: str
    stats: Dict[str, object] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Span]]            # device id -> operations
    modules: Dict[int, List[Span]]        # device id -> program runs
    host: List[Span]                      # bench.* spans


def find(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def short_name(event_name: str) -> str:
    """A device operation's HLO instruction name (the trace gives the
    whole instruction text)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def op_kind(event_name: str) -> str:
    """What a device operation is, without its instance number: the
    instruction's name stripped of ``.N`` suffixes, with a fusion's kind
    (``kOutput`` holds the matmuls)."""
    base = re.sub(r"\.\d+", "", short_name(event_name))
    base = re.sub(r"(remat)\d*", r"\1", base)
    kind = re.search(r"kind=(k\w+)", event_name)
    return f"{base}[{kind.group(1)}]" if kind else base


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` (or an ``.xplane.pb.xz``)."""
    from jax.profiler import ProfileData
    if path.endswith(".xz"):
        import lzma
        with lzma.open(path) as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    ops: Dict[int, List[Span]] = {}
    modules: Dict[int, List[Span]] = {}
    host: List[Span] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in ("XLA Ops", "XLA Modules"):
                dst = (ops if line.name == "XLA Ops" else modules).setdefault(
                    int(m.group(1)), [])
                dst.extend(Span(e.start_ns, e.start_ns + e.duration_ns,
                                short_name(e.name), {"kind": op_kind(e.name)})
                           for e in line.events)
            elif not m:
                host.extend(Span(e.start_ns, e.start_ns + e.duration_ns,
                                 e.name, dict(e.stats))
                            for e in line.events if e.name.startswith("bench."))
    for d in (ops, modules):
        for spans in d.values():
            spans.sort(key=lambda s: s.start)
    host.sort(key=lambda s: s.start)
    return Trace(ops, modules, host)


def union(intervals: Iterable[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """The union of ``intervals`` clipped to ``[lo, hi]``, as sorted,
    disjoint intervals."""
    out: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged: Sequence[Interval], lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` that the disjoint ``merged`` intervals cover."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def host_span_at(host: Sequence[Span], t: float) -> str:
    """The innermost (latest-starting) bench span holding time ``t``."""
    best: Optional[Span] = None
    for s in host:
        if s.start > t:
            break
        if s.end >= t and (best is None or s.start >= best.start):
            best = s
    return best.name if best else "host (outside bench spans)"


def reduce(trace: Trace, kernel_ops: Iterable[str], step_module: str,
           top: int = 10) -> Optional[Dict[str, object]]:
    """The per-layer numbers of the traced window, or None when the trace
    holds no tick or no device operation."""
    ticks = [s for s in trace.host if s.name == "bench.tick"]
    devices = sorted(d for d, o in trace.ops.items() if o)
    if not ticks or not devices:
        return None
    lo, hi = ticks[0].start, ticks[-1].end
    kernel_ops = set(kernel_ops)
    busy = {d: union(((s.start, s.end) for s in trace.ops[d]), lo, hi)
            for d in devices}
    n = len(devices)
    busy_ns = sum(covered(busy[d], lo, hi) for d in devices) / n
    inside = lambda s: s.start >= lo and s.end <= hi
    kernel_ns = sum(s.end - s.start for d in devices for s in trace.ops[d]
                    if inside(s) and s.name in kernel_ops) / n
    steps = [s.end - s.start for d in devices
             for s in trace.modules.get(d, []) if inside(s)
             and s.name.startswith(step_module)]
    d0 = devices[0]
    tick_host = [(t.end - t.start) - covered(busy[d0], t.start, t.end)
                 for t in ticks]
    by_op: Dict[str, float] = {}
    for s in trace.ops[d0]:
        if inside(s):
            kind = str(s.stats.get("kind", s.name))
            by_op[kind] = by_op.get(kind, 0.0) + (s.end - s.start)
    idle = sorted(gaps(busy[d0], lo, hi), key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "kernel_s": kernel_ns * 1e-9,
        "kernel_calls": sum(1 for s in trace.ops[d0]
                            if inside(s) and s.name in kernel_ops),
        "step_s": [x * 1e-9 for x in steps],
        "tick_host_s": [x * 1e-9 for x in tick_host],
        "tick_ids": [int(t.stats.get("tick", -1)) for t in ticks],
        "breakdown": {
            "device_ops": [[k, v * 1e-9] for k, v in sorted(
                by_op.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[host_span_at(trace.host, (a + b) / 2), (b - a) * 1e-9]
                          for a, b in idle],
        },
    }
