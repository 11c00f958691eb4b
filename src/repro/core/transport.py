"""Unified shard_map transport seam — every collective "put" in the repo
goes through here.

Two-Chains (§III) separates *what* a message invokes from *how* it moves;
rFaaS and Seriema (PAPERS.md) both converge on a single transport layer
under many call patterns.  This module is that seam for the JAX port: the
MoE jam transport (``core.dispatch``), the Pallas mailbox ring
(``kernels.mailbox.ops``), and the pipeline-parallel activation ring
(``runtime.pipeline_parallel``) all build their device programs with
``sharded_call`` instead of calling ``shard_map`` directly.  One seam buys:

  1. uniform telemetry — which transports were built, what auto-mode decided,
     how often the injected-mode weight-gather cache hit,
  2. one place to evolve mesh/replication semantics later.
"""
from __future__ import annotations

import dataclasses
import threading
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import jax

from repro.configs.base import MoEConfig
from repro.core import costmodel
from repro.core.costmodel import TransportEstimate


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TransportTelemetry:
    """Process-wide transport counters (trace-time events, cheap to keep)."""

    builds: Dict[str, int] = dataclasses.field(default_factory=dict)
    decisions: List[Tuple[str, TransportEstimate]] = dataclasses.field(
        default_factory=list)
    gather_hits: int = 0
    gather_misses: int = 0

    def record_build(self, label: str) -> None:
        self.builds[label] = self.builds.get(label, 0) + 1

    def record_decision(self, label: str, est: TransportEstimate) -> None:
        self.decisions.append((label, est))

    def summary(self) -> str:
        builds = " ".join(f"{k}={v}" for k, v in sorted(self.builds.items()))
        modes: Dict[str, int] = {}
        for _, est in self.decisions:
            modes[est.chosen] = modes.get(est.chosen, 0) + 1
        chose = " ".join(f"{k}:{v}" for k, v in sorted(modes.items()))
        return (f"builds[{builds}] auto[{chose or '-'}] "
                f"gather_cache[hit={self.gather_hits} "
                f"miss={self.gather_misses}]")


_TELEMETRY = TransportTelemetry()
_LOCK = threading.Lock()


def get_telemetry() -> TransportTelemetry:
    return _TELEMETRY


def reset_telemetry() -> TransportTelemetry:
    """Zero the counters (tests); returns the fresh object."""
    global _TELEMETRY
    with _LOCK:
        _TELEMETRY = TransportTelemetry()
    return _TELEMETRY


# ---------------------------------------------------------------------------
# the seam
# ---------------------------------------------------------------------------

def sharded_call(body: Callable, mesh, in_specs, out_specs, *,
                 label: str = "transport",
                 check_replication: bool = False) -> Callable:
    """Build a shard_map'd callable — the repo's one ``jax.shard_map``.

    ``label`` names the call site in telemetry.  ``check_replication`` maps
    to ``check_vma``; the repo's transports hand-manage replication, so it
    defaults off.
    """
    with _LOCK:
        _TELEMETRY.record_build(label)
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs,
                            check_vma=check_replication)


# ---------------------------------------------------------------------------
# mode decision (pure — testable without devices)
# ---------------------------------------------------------------------------

def choose_transport_mode(m: MoEConfig, *, d_model: int, batch: int, seq: int,
                          mesh_shape: Mapping[str, int],
                          dp_axes: Sequence[str], tp_axis: str, mode: str,
                          dtype_bytes: int = 2, weight_reuse: int = 1,
                          label: str = "jam",
                          log_choice: Optional[list] = None
                          ) -> Tuple[str, Optional[TransportEstimate]]:
    """Resolve ``mode`` ('local'|'injected'|'tp'|'auto') for one call shape.

    The cost model sees the **per-dp-shard** token count — the tokens that
    actually enter one shard body — not the global ``batch*seq`` (which
    would inflate local-mode byte estimates by the dp factor and mis-place
    the local/injected crossover).  Any non-tp choice degrades to 'tp' when
    the per-shard token count cannot split over the tensor axis; the
    recorded estimate reflects the mode that actually executes, never a
    pre-degrade preference.
    """
    tp = mesh_shape[tp_axis]
    dp = 1
    for a in dp_axes:
        dp *= mesh_shape.get(a, 1)
    n_per_shard = (batch * seq) // max(1, dp)

    est: Optional[TransportEstimate] = None
    chosen = mode
    if mode == "auto":
        est = costmodel.estimate_transport(
            m, d_model=d_model, n_tokens_per_dp_shard=n_per_shard, tp=tp,
            dtype_bytes=dtype_bytes, weight_reuse=weight_reuse)
        chosen = est.chosen
    if chosen != "tp" and (n_per_shard % tp != 0 or n_per_shard < tp):
        chosen = "tp"
    if mode == "auto":
        if est.chosen != chosen:                  # divisibility degrade won
            est = dataclasses.replace(est, chosen=chosen)
        with _LOCK:
            _TELEMETRY.record_decision(label, est)
        if log_choice is not None:
            log_choice.append(est)
    return chosen, est


# ---------------------------------------------------------------------------
# injected-mode weight-gather cache
# ---------------------------------------------------------------------------

class WeightGatherCache:
    """Identity-keyed memo for injected-mode weight all-gathers.

    Superseded in the live MoE path by the named lease pool
    (``repro.fabric.leases``), which inherits these identity/tracer
    semantics; kept as the minimal reference implementation the lease
    tests pin against.

    The cost model amortizes the weight gather over ``weight_reuse``
    invocations (gradient-accumulation microbatches, decode ticks); this
    cache realizes the amortization: repeated transport calls on the *same*
    weight arrays — same concrete arrays across eager calls, or same tracers
    within one trace — reuse the gathered result instead of re-gathering.

    Entries hold strong references to their key arrays, so a cached id can
    never be recycled by a new object while its entry is live; hits are
    re-verified with ``is``.  Bounded LRU so stale trace tracers cannot
    accumulate.

    Tracer safety: an entry whose value contains tracers is stored only
    when the key arrays are themselves tracers of that same trace — then a
    hit requires the identical (still-live) tracer objects.  A traced value
    produced from *concrete* keys (a jit closure capturing the weights) is
    NOT cached: a later eager call with those same concrete arrays would
    otherwise receive a dead trace's tracer (UnexpectedTracerError).
    """

    def __init__(self, capacity: int = 4):
        self.capacity = capacity
        self._entries: "OrderedDict[Tuple[int, ...], Tuple[tuple, Any]]" = \
            OrderedDict()

    def get_or_gather(self, key_arrays: Sequence[Any],
                      gather: Callable[[], Any]) -> Any:
        key = tuple(id(a) for a in key_arrays)
        hit = self._entries.get(key)
        if hit is not None and all(a is b for a, b in
                                   zip(hit[0], key_arrays)):
            self._entries.move_to_end(key)
            with _LOCK:
                _TELEMETRY.gather_hits += 1
            return hit[1]
        with _LOCK:
            _TELEMETRY.gather_misses += 1
        value = gather()
        value_traced = any(isinstance(leaf, jax.core.Tracer)
                           for leaf in jax.tree.leaves(value))
        keys_traced = any(isinstance(a, jax.core.Tracer)
                          for a in key_arrays)
        if value_traced and not keys_traced:
            return value            # closure-captured trace: do not cache
        self._entries[key] = (tuple(key_arrays), value)
        while len(self._entries) > self.capacity:
            self._entries.popitem(last=False)
        return value
