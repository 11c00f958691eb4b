"""The one traffic generator: a mix file in, a seeded request list out.

A mix (``bench/traffic/<name>.json``) holds only parameters:

  loop          "open" (arrivals on a schedule) or "closed" (clients that
                each send their next request when the last one finished)
  rate_per_s    open loop: mean arrivals per second (Poisson)
  bursts        open loop, optional: {"period_s", "on_share"}: arrivals
                come only in the first ``on_share`` of every period, at
                ``rate_per_s / on_share``, so the mean rate is kept (on/off
                bursts); without it arrivals are Poisson all through
  clients       closed loop: how many clients
  slots         request rows the engine serves at once
  prompt_len    {"median", "sigma", "min", "max"}: lognormal, clipped
  output_len    the same, for the tokens generated
  limits        {"ttft_ms": {"base", "per_prompt_token"}, "itl_ms"}: what a
                request must meet to count towards ``slo_pct``
  drain_s       open loop: how long a request due in the window is followed
                after the window closes before it counts as failed
  requests_per_client  closed loop: how many requests each client has ready

Every seed gets the same multiset of prompt lengths, output lengths and
gaps between arrivals (stratified quantiles of the distributions). In an
open loop the seed orders them; in a closed loop each client's turns are
the same for every seed and the seed only relabels the clients. The seed
also draws the token ids. So the work of a run is fixed and seeds differ
in arrangement, not in amount.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request as the generator plans it."""

    rid: int
    prompt: np.ndarray          # (L,) int32
    max_new: int
    arrival_s: float = 0.0      # open loop: offset from the window's start
    client: int = -1            # closed loop: the client that sends it


def lognormal_quantiles(spec: Dict[str, Any], n: int) -> List[int]:
    """``n`` stratified quantiles of the clipped lognormal ``spec``."""
    mu, sigma = math.log(spec["median"]), float(spec["sigma"])
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    return [int(min(spec["max"], max(spec["min"], round(math.exp(mu + sigma * q)))))
            for q in z]


def exponential_gaps(rate: float, n: int) -> List[float]:
    """``n`` stratified quantiles of the gap between Poisson arrivals."""
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


def count(mix: Dict[str, Any], seconds: float) -> int:
    if mix["loop"] == "open":
        return max(1, round(mix["rate_per_s"] * seconds))
    return mix["clients"] * mix["requests_per_client"]


def generate(mix: Dict[str, Any], seed: int, seconds: float, vocab: int,
             rid0: int = 0) -> List[Planned]:
    """The requests of one run. Open loop: arrivals inside ``[0, seconds)``.
    Closed loop: ``requests_per_client`` requests for each client, in the
    order that client sends them."""
    if mix["loop"] not in ("open", "closed"):
        raise ValueError(f"loop must be 'open' or 'closed', got {mix['loop']!r}")
    n = count(mix, seconds)
    rng = np.random.default_rng(seed)
    P = lognormal_quantiles(mix["prompt_len"], n)
    O = lognormal_quantiles(mix["output_len"], n)
    if mix["loop"] == "closed":
        # a fixed deal of sizes to (client, turn), the same for every
        # seed; the seed only relabels the clients. A closed-loop window
        # holds a client's first turns alone, so any other order would
        # change the work in it from seed to seed.
        deal = np.random.default_rng(0)
        prompts, outputs = deal.permutation(P), deal.permutation(O)
        relabel = rng.permutation(mix["clients"])
    else:
        prompts, outputs = rng.permutation(P), rng.permutation(O)
    out = [Planned(rid=rid0 + i,
                   prompt=rng.integers(0, vocab, size=int(prompts[i]),
                                       dtype=np.int32),
                   max_new=int(outputs[i]))
           for i in range(n)]
    if mix["loop"] == "open":
        # arrivals are laid out on a clock that runs only while arrivals
        # are on (all the window, without bursts), then spread over the
        # periods
        burst = mix.get("bursts")
        on_total, rate = seconds, mix["rate_per_s"]
        if burst:
            period = float(burst["period_s"])
            on = period * float(burst["on_share"])
            on_total = (seconds // period) * on + min(seconds % period, on)
            rate = rate * seconds / on_total
        gaps = rng.permutation(exponential_gaps(rate, n))
        # the first request arrives one gap after the window opens; the
        # last no later than the window's end
        t = np.cumsum(gaps) * (on_total / max(on_total, float(np.sum(gaps))))
        if burst:
            t = (t // on) * period + t % on
        for p, a in zip(out, t):
            p.arrival_s = float(min(a, seconds * (1 - 1e-9)))
    else:
        for i, p in enumerate(out):
            p.client = int(relabel[i % mix["clients"]])
    return out
