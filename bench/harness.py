"""One run of one cell: set up, warm up, measure, check.

The entry under test is the paged ``Engine`` as the serving launcher builds
it (``repro.launch.serve.build_engine``, ``kernel="auto"``) on a 1x1 mesh.
The prefill chunk is the program's own default (``DEFAULT_CHUNK``); the
slots, ``max_len`` and pool size are the cell's deployment settings. The
weights are made on the device from the seed in one jitted call (the
configuration's block, ``bench/blocks/<block>/weights.py``) and installed
with ``Engine.load_params``.

After set-up and a warm-up on the cell's own traffic, the window is driven
only through ``Engine.submit`` and ``Engine.tick``. Times are taken by the
host clock at tick boundaries: a token's time is the end of the tick that
produced it, and a request's admission time is the start of the tick in
whose admission it appears in ``Engine.admission_log``. Each tick runs
inside a ``bench.tick`` profiler span, so a traced run can put the host's
time beside the device's.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

import check
import traffic as traffic_lib
from spec import BENCH, ROOT, Cell, cell_metrics

TRACE_FRACTION = (0.4, 0.3)   # a traced run traces from 40% of the window, for 30%
TRACE_MAX_S = 5.0             # ... and for at most this long


@dataclasses.dataclass
class ReqRecord:
    """What the harness saw of one request (times in seconds from the
    window's opening)."""

    planned: traffic_lib.Planned
    sent: float                          # open loop: its scheduled arrival
    submitted: Optional[float] = None    # when Engine.submit was called
    req: Any = None                      # the program's Request
    admitted: Optional[float] = None
    token_times: List[float] = dataclasses.field(default_factory=list)
    refused: bool = False

    @property
    def done(self) -> bool:
        return self.req is not None and self.req.done

    def lateness(self) -> Optional[float]:
        """How late the harness handed the request to the engine: an open
        loop's arrivals wait for the tick that is running."""
        return None if self.submitted is None else self.submitted - self.sent

    def ttft(self) -> Optional[float]:
        return self.token_times[0] - self.sent if self.token_times else None

    def gaps(self) -> List[float]:
        t = self.token_times
        return [b - a for a, b in zip(t, t[1:])]


@dataclasses.dataclass
class Tick:
    start: float
    end: float
    rows: int                            # Engine.tick()'s return value
    work: Optional[List[Tuple[int, int]]] = None   # traced: (start, n) rows
    emitted: int = 0


@dataclasses.dataclass
class Run:
    """Everything a metric reader (``bench/metrics/<name>.py``) may read."""

    cell: Cell
    seconds: float
    setup_s: float
    window_s: float = 0.0
    requests: List[ReqRecord] = dataclasses.field(default_factory=list)
    ticks: List[Tick] = dataclasses.field(default_factory=list)
    preemptions: int = 0
    compiles: int = 0                    # XLA compilations inside the window
    memory_peak_bytes: Optional[int] = None   # the chip's, as JAX reports it
    pool_blocks: int = 0                 # KV pool size, in blocks
    pool_peak_blocks: int = 0            # most blocks in use at once
    trace: Optional[Dict[str, Any]] = None
    peak: Optional[Dict[str, float]] = None
    check: Optional[Dict[str, Any]] = None    # check.compare's readings

    @property
    def geometry(self):
        return self.cell.geometry

    @property
    def limits(self) -> Dict[str, Any]:
        return self.cell.traffic["limits"]

    @property
    def open_loop(self) -> bool:
        return self.cell.traffic["loop"] == "open"

    def due(self) -> List[ReqRecord]:
        """Requests due in the window: open loop, every arrival in it;
        closed loop, every request sent in it."""
        return [r for r in self.requests if r.sent < self.seconds]

    def traced_ticks(self) -> List[Tick]:
        if self.trace is None:
            return []
        return [self.ticks[i] for i in self.trace["ticks"]]


def percentile(values, q: float) -> Optional[float]:
    """numpy's linear-interpolation percentile; None for no values."""
    return float(np.percentile(np.asarray(values, float), q)) if len(values) else None


def step_rows(before, slot_entry) -> List[Tuple[int, int]]:
    """The rows ``(start, n)`` a tick fed, from the requests' resident
    token counts before and after it: a request that ran advanced from
    ``start`` to ``start + n``; one admitted in the tick started at 0; one
    preempted in it went back to 0 and did not run."""
    pos = {id(e): p for e, p in before}
    entries = {id(e): e for e, _ in before}
    entries.update((id(e), e) for e in slot_entry if e is not None)
    rows = []
    for key, e in entries.items():
        start = pos.get(key, 0)
        if e.pos > start:
            rows.append((start, e.pos - start))
    return rows


def load_reader(name: str) -> Callable[[Run], Optional[float]]:
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    unless ``JAX_COMPILATION_CACHE_DIR`` names one; every program, however
    small, is kept."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts XLA compilations (cache loads included) while ``armed``."""

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if self.armed and event.endswith("backend_compile_duration"):
            self.count += 1


class Server:
    """The engine of one cell with its weights, and the loop that drives
    it; ``measure`` may be called once per seed after one set-up."""

    def __init__(self, cell: Cell, *, on_tpu: bool = True,
                 engine_hook: Optional[Callable[[Any], None]] = None,
                 devices: Optional[list] = None):
        from repro.launch.mesh import make_local_mesh
        from repro.launch.serve import DEFAULT_CHUNK, build_engine

        self.cell = cell
        self.on_tpu = on_tpu
        self.cfg = cell.block().model_config(cell.geometry,
                                             cell.config["serving"]["dtype"])
        self.mesh = make_local_mesh((1, 1), devices=devices)
        self.chunk = DEFAULT_CHUNK
        with self.mesh:
            self.engine = build_engine(
                self.cfg, self.mesh, cache="paged", slots=cell.slots,
                max_len=cell.max_len, num_blocks=cell.num_blocks(),
                block_size=cell.block_size, chunk=DEFAULT_CHUNK,
                kernel="auto")
        self.kernel_ops: List[str] = []
        if on_tpu:
            if self.engine.paged_kernel != "pallas":
                raise RuntimeError(f"kernel='auto' resolved to "
                                   f"{self.engine.paged_kernel!r}, not pallas")
            text = self.engine.lower_step().compile().as_text()
            self.kernel_ops = check.custom_call_names(text)
            if not self.kernel_ops:
                raise RuntimeError("the compiled step holds no Mosaic kernel "
                                   "(no tpu_custom_call)")
        if engine_hook is not None:
            engine_hook(self.engine)
        self._make = None

    def load(self, seed: int) -> None:
        """Weights from ``seed``, made on the device and installed."""
        import jax
        W = self.cell.block("weights")
        eng = self.engine
        eng.params = eng.cache = None          # free the last seed's first
        gc.collect()
        if self._make is None:
            g = self.cell.geometry
            self._make = jax.jit(lambda lo, hi: W.make_params(g, lo, hi),
                                 out_shardings=eng.pshard)
        params = self._make(*W.seed_words(seed))
        want = jax.tree.map(lambda s: (s.shape, s.dtype), eng.params_shapes)
        got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
        if want != got:
            raise RuntimeError("the block's weights.py no longer matches "
                               "the program's parameter layout")
        with self.mesh:
            eng.load_params(params)
        jax.block_until_ready((eng.params, eng.cache))

    def warm_up(self, planned: List[traffic_lib.Planned]) -> None:
        """Serve two of the cell's own prompts, cut short, to the end: the
        step program (one shape for prefill and decode) is loaded or
        compiled, and so is every host path the window takes."""
        from repro.engine import Request
        eng = self.engine
        with self.mesh:
            for i, p in enumerate(planned[:2]):
                eng.submit(Request(-1 - i, p.prompt[:3 * self.chunk],
                                   max_new_tokens=3))
            while eng.pending():
                eng.tick()
        eng.completed.clear()

    def free(self) -> None:
        """Drop the weights and the pool, so that the reference fits."""
        self.engine.params = self.engine.cache = None
        gc.collect()

    def measure(self, planned: List[traffic_lib.Planned], seconds: float,
                run: Run, trace_dir: Optional[str] = None,
                counter: Optional[CompileCounter] = None,
                process_start: Optional[float] = None) -> None:
        """Drive the window into ``run``. An open loop's requests due in the
        window are followed after it closes, up to ``drain_s``. A closed
        loop's clients send their first requests before the window opens
        and are served until every first prompt is filled, so the window
        opens on long contexts decoding; that filling is set-up. The
        window opens when this returns control to the loop:
        ``run.setup_s`` is taken there from ``process_start``."""
        import jax
        from repro.engine import Request

        eng, mix = self.engine, self.cell.traffic
        closed = mix["loop"] == "closed"
        recs = {p.rid: ReqRecord(p, sent=float("nan")) for p in planned}
        active: List[ReqRecord] = []
        seen_admitted = len(eng.admission_log)
        trace_at = seconds * TRACE_FRACTION[0]
        trace_len = min(TRACE_MAX_S, seconds * TRACE_FRACTION[1])
        tracing = in_window = False
        clock = time.perf_counter
        queues: Dict[int, List[traffic_lib.Planned]] = {}

        def submit(rec: ReqRecord, sent: float) -> None:
            p = rec.planned
            rec.sent = sent
            req = Request(p.rid, p.prompt, max_new_tokens=p.max_new)
            run.requests.append(rec)
            rec.submitted = clock()
            with jax.profiler.TraceAnnotation("bench.submit"):
                try:
                    eng.submit(req)
                except ValueError:
                    rec.refused = True
                    return
            rec.req = req
            active.append(rec)

        def one_tick() -> None:
            nonlocal seen_admitted
            before = None
            if tracing:
                before = [(e, e.pos) for e in eng.slot_entry if e is not None]
            t_a = clock()
            with jax.profiler.TraceAnnotation("bench.tick", tick=len(run.ticks)):
                rows = eng.tick()
            t_b = clock()
            tick = Tick(t_a, t_b, rows)
            with jax.profiler.TraceAnnotation("bench.record"):
                log = eng.admission_log
                while seen_admitted < len(log):
                    rec = recs.get(log[seen_admitted])
                    if rec is not None:
                        rec.admitted = t_a
                    seen_admitted += 1
                finished = []
                for rec in active:
                    n = len(rec.req.out_tokens)
                    while len(rec.token_times) < n:
                        rec.token_times.append(t_b)
                        tick.emitted += 1
                    if rec.req.done:
                        finished.append(rec)
                for rec in finished:
                    active.remove(rec)
                    if closed and in_window:
                        send_next(rec.planned.client)
                if before is not None:
                    tick.work = step_rows(before, eng.slot_entry)
            if in_window:
                run.ticks.append(tick)

        def send_next(client: int) -> None:
            q = queues[client]
            if not q:
                raise RuntimeError(f"client {client} ran out of requests: "
                                   "raise requests_per_client")
            submit(recs[q.pop(0).rid], clock())

        with self.mesh:
            if closed:
                for p in planned:
                    queues.setdefault(p.client, []).append(p)
                firsts = []
                for c in sorted(queues):
                    send_next(c)
                    firsts.append(run.requests[-1])
                while any(not r.token_times for r in firsts):
                    one_tick()
            in_window = True
            preempt0 = eng.preempt_count
            t0 = clock()
            if process_start is not None:
                run.setup_s = t0 - process_start
            if counter is not None:
                counter.count, counter.armed = 0, True
            if closed:
                for c in sorted(queues):         # clients that finished in set-up
                    if not any(r.planned.client == c for r in active):
                        send_next(c)
                while clock() - t0 < seconds:
                    tracing = self._trace_switch(trace_dir, tracing,
                                                 clock() - t0, trace_at, trace_len)
                    one_tick()
                run.window_s = clock() - t0
                tracing = self._trace_switch(trace_dir, tracing, 1e30, 0, 0)
            else:
                order = sorted(planned, key=lambda p: p.arrival_s)
                i = 0
                while True:
                    now = clock() - t0
                    if now >= seconds:
                        break
                    tracing = self._trace_switch(trace_dir, tracing, now,
                                                 trace_at, trace_len)
                    while i < len(order) and order[i].arrival_s <= now:
                        submit(recs[order[i].rid], t0 + order[i].arrival_s)
                        i += 1
                    if eng.pending():
                        one_tick()
                    else:
                        nxt = order[i].arrival_s if i < len(order) else seconds
                        with jax.profiler.TraceAnnotation("bench.idle"):
                            time.sleep(max(0.0, min(nxt, seconds) - now))
                run.window_s = clock() - t0
                tracing = self._trace_switch(trace_dir, tracing, 1e30, 0, 0)
                for p in order[i:]:              # due while the last tick ran
                    submit(recs[p.rid], t0 + p.arrival_s)
                cap = t0 + seconds + float(mix["drain_s"])
                while active and clock() < cap:
                    one_tick()
        if counter is not None:
            counter.armed = False
        for rec in run.requests:                 # times from the window's opening
            rec.sent -= t0
            rec.submitted -= t0
            if rec.admitted is not None:
                rec.admitted -= t0
            rec.token_times = [t - t0 for t in rec.token_times]
        for tick in run.ticks:
            tick.start -= t0
            tick.end -= t0
        run.preemptions = eng.preempt_count - preempt0
        run.compiles = counter.count if counter is not None else 0
        run.pool_blocks = eng.num_blocks
        run.pool_peak_blocks = eng.peak_blocks_used

    @staticmethod
    def _trace_switch(trace_dir, tracing, now, at, length) -> bool:
        import jax
        if trace_dir is None:
            return False
        if not tracing and at <= now < at + length:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # bench.* spans and XLA only
            opts.host_tracer_level = 2
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            return True
        if tracing and now >= at + length:
            jax.profiler.stop_trace()
            return False
        return tracing




def run_once(cell: Cell, seed: int, seconds: float, *,
             process_start: float, trace_dir: Optional[str] = None,
             on_tpu: bool = True, metrics: Optional[list] = None,
             engine_hook: Optional[Callable[[Any], None]] = None,
             control: bool = False) -> Tuple[Dict[str, Any], List[str]]:
    """Set up, warm up, measure and check one run; returns the result
    object and the lines for standard error (the numbers compared last)."""
    server = Server(cell, on_tpu=on_tpu, engine_hook=engine_hook)
    result, lines, _ = run_seed(server, seed, seconds, counter=CompileCounter(),
                                process_start=process_start,
                                trace_dir=trace_dir, metrics=metrics,
                                control=control)
    return result, lines


def run_seed(server: Server, seed: int, seconds: float, *,
             counter: Optional[CompileCounter] = None,
             process_start: Optional[float] = None,
             trace_dir: Optional[str] = None, metrics: Optional[list] = None,
             control: bool = False, warm: bool = True
             ) -> Tuple[Dict[str, Any], List[str], Run]:
    """One seed on a server that is set up: weights from the seed, the
    warm-up (``warm``), the window, the check and the metrics.

    With ``control`` the check's control stands in the program's place:
    the tokens that the float32 reference judges are the fp8 reference's
    first choices at the positions where the program served a token, so
    ``correct`` reads what the control reads against the cell's own
    limit. The benchmark's own runs never set it."""
    import jax
    import trace_reduce
    import work

    cell = server.cell
    server.load(seed)
    planned = traffic_lib.generate(cell.traffic, seed, seconds,
                                   cell.geometry.vocab)
    if warm:
        server.warm_up(planned)
    run = Run(cell, seconds, setup_s=0.0)
    server.measure(planned, seconds, run, trace_dir=trace_dir, counter=counter,
                   process_start=process_start)
    dev = jax.devices()[0]
    run.memory_peak_bytes = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": run.memory_peak_bytes}
    if server.on_tpu:
        run.peak = work.peaks(dev.device_kind)
    if trace_dir is not None:
        reduced = trace_reduce.reduce(
            trace_reduce.load(trace_reduce.find(trace_dir)),
            server.kernel_ops, "jit_paged_step")
        if reduced is not None:
            reduced["ticks"] = [i for i in reduced["tick_ids"]
                                if 0 <= i < len(run.ticks)
                                and run.ticks[i].work is not None]
            run.trace = reduced
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]

    server.free()
    due = run.due()
    finished = [r for r in due if r.done]
    short = sum(1 for r in finished if len(r.req.out_tokens) != r.planned.max_new)
    limit = float(cell.config["correct"]["max_gap"])
    t_ref = time.perf_counter()
    run.check = got = check.compare(cell, seed, check.pick(finished, seed),
                                    control=control)
    t_ref = time.perf_counter() - t_ref
    gap = got["control_gap"] if control else got["max_gap"]
    correct = gap is not None and gap <= limit and short == 0

    values = {}
    for m in metrics if metrics is not None else cell_metrics(
            cell.name, trace_dir is not None):
        v = load_reader(m["name"])(run)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    failed = sum(1 for r in due if r.refused or (run.open_loop and not r.done))
    result: Dict[str, Any] = {
        "correct": bool(correct), "attempted": len(due), "failed": failed,
        "metrics": values, "device": device,
    }
    if run.trace is not None:
        result["breakdown"] = run.trace["breakdown"]
    result["compiles_in_window"] = run.compiles
    result["compared"] = {
        "max_gap": {"value": gap, "limit": limit},
        "short_outputs": {"value": short, "limit": 0},
    }
    lines = [
        f"{cell.name} seed {seed}: {len(due)} requests due, "
        f"{len(finished)} finished, {failed} failed, {len(run.ticks)} ticks "
        f"in {run.window_s:.3f} s (+ follow-up), set-up {run.setup_s:.3f} s, "
        f"compiles in the window {run.compiles}, KV pool blocks in use at "
        f"most {run.pool_peak_blocks} of {run.pool_blocks}",
        f"checked {got['tokens']} served tokens of "
        f"{min(len(finished), check.SAMPLE)} requests against the float32 "
        f"reference in {t_ref:.1f} s",
    ]
    if control:
        lines.append(f"control: the fp8 reference's first choices stand in the "
                     f"served tokens' place (the served tokens read max_gap "
                     f"{got['max_gap']})")
    lines += [f"compared max_gap {gap} limit {limit}",
              f"compared short_outputs {short} limit 0"]
    return result, lines, run
