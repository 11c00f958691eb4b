"""The engine's host spans and the step's named scopes in a profiler trace.

``trace_reduce`` reads the harness's ``bench.*`` spans and the device's
timelines; this module reads, from the same ``.xplane.pb``, the engine's
own spans (``engine.*``, docs/engine.md#tracing) beside them, and charges
the step's device time to the program's named scopes. It is a tool run by
hand (``span_report.py``), not a benchmark metric.

``scope_map`` reads the compiled step's HLO text: which named scope of the
program (``SCOPES``) each operation belongs to. ``reduce`` takes the window
from the first traced ``bench.tick`` span's start to the last one's end,
and gives:

  span_s         per host span name, its self time (its length minus its
                 children's) in the window over the traced ticks
  tick_prep_s    per ``engine.tick`` that ran a step, from its start to its
                 ``engine.dispatch``'s end: host time before the tick's
                 step is launched, which the device waits through
  scope_s        device time, inside the step's runs, of the operations of
                 each named scope (by ``scope_map``)
  unscoped_s     the same for the operations the scope map leaves out
  step_s         device time of each run of the step program
  idle_by_span   the device's idle time in the window, each instant
                 charged to the innermost host span that holds it
  idle_gaps      the longest idle gaps, each named by the innermost host
                 span that holds most of it
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

import trace_reduce as tr
from trace_reduce import Span, Trace

HOST_SPANS = ("bench.", "engine.")
OUTSIDE = "host (outside bench spans)"

# the program's named scopes in the paged step (models/, runtime/steps.py)
SCOPES = ("embed", "attention", "kv_pool", "mlp", "head")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERANDS = re.compile(r"\s[a-z][\w\-]*\(([^)]*)\)")
_OPERAND = re.compile(r"%([\w.\-]+)")


def load(path: str) -> Trace:
    """``trace_reduce.load``'s timelines, with the engine's host spans
    kept beside the harness's."""
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
        m = tr.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in ("XLA Ops", "XLA Modules"):
                dst = (ops if line.name == "XLA Ops" else modules).setdefault(
                    int(m.group(1)), [])
                dst.extend(Span(e.start_ns, e.start_ns + e.duration_ns,
                                tr.short_name(e.name)) for e in line.events)
            elif not m:
                host.extend(Span(e.start_ns, e.start_ns + e.duration_ns,
                                 e.name, dict(e.stats))
                            for e in line.events
                            if e.name.startswith(HOST_SPANS))
    for spans in [*ops.values(), *modules.values(), host]:
        spans.sort(key=lambda s: s.start)
    return Trace(ops, modules, host)


def scope_map(hlo_text: str) -> Dict[str, str]:
    """The named scope of each operation of a compiled program's ENTRY
    computation, by instruction name. An instruction's ``op_name`` names
    its scope by its innermost segment that is one of ``SCOPES``; one
    without an ``op_name`` (XLA's copies, rematerialisations, async
    halves, bitcasts) takes its first operand's scope, followed back until
    an instruction has one. Instructions left without a scope are not in
    the map."""
    at = hlo_text.find("\nENTRY ")
    if at < 0:
        return {}
    info: Dict[str, Tuple[Optional[str], Optional[str]]] = {}
    for line in hlo_text[at + 1:].splitlines()[1:]:
        if line.startswith("}"):
            break
        m = _INSTR.match(line)
        if not m:
            continue
        name, rhs = m.groups()
        op = _OP_NAME.search(rhs)
        args = _OPERANDS.search(" " + rhs)
        first = _OPERAND.search(args.group(1)) if args else None
        info[name] = (op.group(1) if op else None,
                      first.group(1) if first else None)

    def own(op_name: str) -> Optional[str]:
        return next((seg for seg in reversed(op_name.split("/"))
                     if seg in SCOPES), None)

    out: Dict[str, str] = {}
    for name in info:
        chain, cur, scope = [], name, None
        while cur in info and cur not in chain:
            if cur in out:
                scope = out[cur]
                break
            chain.append(cur)
            op_name, first = info[cur]
            if op_name is not None:
                scope = own(op_name)
                break
            cur = first
        if scope is not None:
            out.update((n, scope) for n in chain)
    return out


def _nested(spans: Sequence[Span]):
    """``(span, parent)`` for each span, in start order; host spans come
    from one thread, so they nest."""
    stack: List[Span] = []
    for s in sorted(spans, key=lambda s: (s.start, -s.end)):
        while stack and stack[-1].end <= s.start:
            stack.pop()
        yield s, (stack[-1] if stack else None)
        stack.append(s)


def innermost(host: Sequence[Span], lo: float, hi: float
              ) -> List[Tuple[float, float, str]]:
    """``[lo, hi]`` cut into disjoint pieces, each named by the innermost
    host span that holds it."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Span] = []
    t = lo

    def upto(end: float) -> None:
        nonlocal t
        end = min(end, hi)
        if end > t:
            out.append((t, end, stack[-1].name if stack else OUTSIDE))
            t = end

    for s in sorted(host, key=lambda s: (s.start, -s.end)):
        if s.end <= lo or s.start >= hi:
            continue
        while stack and stack[-1].end <= s.start:
            upto(stack[-1].end)
            stack.pop()
        upto(s.start)
        stack.append(s)
    while stack:
        upto(stack[-1].end)
        stack.pop()
    upto(hi)
    return out


def reduce(trace: Trace, step_module: str,
           scopes: Optional[Dict[str, str]] = None, top: int = 10
           ) -> Optional[Dict[str, object]]:
    """The host phases and device scopes of the traced window, or None
    when the trace holds no tick or no device operation. ``scopes`` is the
    step's ``scope_map``; without it ``scope_s`` is empty."""
    ticks = [s for s in trace.host if s.name == "bench.tick"]
    devices = sorted(d for d, o in trace.ops.items() if o)
    if not ticks or not devices:
        return None
    lo, hi = ticks[0].start, ticks[-1].end
    n = len(devices)
    inside = lambda s: s.start >= lo and s.end <= hi
    d0 = devices[0]
    busy = tr.union(((s.start, s.end) for s in trace.ops[d0]), lo, hi)

    spans = [s for s in trace.host if inside(s)]
    self_ns: Dict[str, float] = {}
    for s, parent in _nested(spans):
        self_ns[s.name] = self_ns.get(s.name, 0.0) + (s.end - s.start)
        if parent is not None:
            self_ns[parent.name] -= s.end - s.start
    prep = []
    for t in (s for s in spans if s.name == "engine.tick"):
        launch = [s.end for s in spans if s.name == "engine.dispatch"
                  and t.start <= s.start and s.end <= t.end]
        if launch:
            prep.append(launch[0] - t.start)

    steps: List[float] = []
    scope_ns: Dict[str, float] = {}
    unscoped_ns = 0.0
    for d in devices:
        runs = [r for r in trace.modules.get(d, [])
                if inside(r) and r.name.startswith(step_module)]
        if d == d0:
            steps = [r.end - r.start for r in runs]
        if scopes is None:
            continue
        i = 0
        for s in trace.ops[d]:
            while i < len(runs) and runs[i].end < s.end:
                i += 1
            if i == len(runs):
                break
            if s.start < runs[i].start:
                continue
            scope = scopes.get(s.name)
            if scope is None:
                unscoped_ns += (s.end - s.start) / n
            else:
                scope_ns[scope] = scope_ns.get(scope, 0.0) + (s.end - s.start) / n

    # each idle instant is charged to the innermost host span holding it
    idle = tr.gaps(busy, lo, hi)
    pieces = innermost(trace.host, lo, hi)
    charged: List[Dict[str, float]] = []
    idle_ns: Dict[str, float] = {}
    j = 0
    for a, b in idle:
        while pieces[j][1] <= a:
            j += 1
        share: Dict[str, float] = {}
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            p0, p1, name = pieces[k]
            share[name] = share.get(name, 0.0) + min(b, p1) - max(a, p0)
            idle_ns[name] = idle_ns.get(name, 0.0) + min(b, p1) - max(a, p0)
            k += 1
        charged.append(share)
    longest = sorted(range(len(idle)),
                     key=lambda i: idle[i][0] - idle[i][1])[:top]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": tr.covered(busy, lo, hi) * 1e-9,
        "ticks": len(ticks),
        "step_s": [x * 1e-9 for x in steps],
        "span_s": {k: v * 1e-9 / len(ticks) for k, v in sorted(self_ns.items())},
        "tick_prep_s": [x * 1e-9 for x in prep],
        "scope_s": {k: v * 1e-9 for k, v in sorted(scope_ns.items())},
        "unscoped_s": unscoped_ns * 1e-9,
        "idle_by_span": {k: v * 1e-9 for k, v in sorted(
            idle_ns.items(), key=lambda kv: -kv[1])},
        "idle_gaps": [[max(charged[i], key=charged[i].get),
                       (idle[i][1] - idle[i][0]) * 1e-9] for i in longest],
    }
