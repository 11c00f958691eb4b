"""Cluster launcher: a Router over N engine replicas, with live migration.

  # two paged llama replicas, forced migration after 3 router ticks:
  PYTHONPATH=src python -m repro.launch.serve_cluster --smoke \
      --replicas llama3.2-1b:paged,llama3.2-1b:paged --migrate-after 3

  # heterogeneous fleet (mixed models + backends, priority scheduling):
  PYTHONPATH=src python -m repro.launch.serve_cluster --smoke \
      --replicas llama3.2-1b:paged,llama3.2-1b:paged,mamba-130m:recurrent \
      --scheduler priority --requests 9 --migrate-after 2

  # chaos smoke (CI): seeded frame corruption + a replica kill; the run
  # serves a noise-free baseline first, replays the same requests under
  # the fault plan, and exits 1 unless every output is bitwise identical:
  PYTHONPATH=src python -m repro.launch.serve_cluster --smoke \
      --replicas llama3.2-1b:paged,llama3.2-1b:paged --migrate-after 3 \
      --fault-rate 0.3 --fault-seed 7 --kill-after 5

Replica ``i`` serves on its own one-device mesh over ``jax.devices()[i %
n]``: on a four-chip host, four replicas take one chip each. Without
``--smoke`` each arch serves at its published widths.

Each ``--replicas`` entry is ``arch:cache`` (cache one of
paged/slots/recurrent/auto). Replicas of the same arch share one weight
tree, installed via ``Engine.inject_params`` (which places it on each
replica's own device) so every replica's params
lease is warm and ``placement="auto"`` resolves to injected from the
first tick — the router's cost model then places by load alone among
warm replicas. Requests round through ``Router.submit`` with a priority
spread; ``--migrate-after N`` forcibly live-migrates one in-flight
request between compatible replicas after N router ticks (exits non-zero
if no migration could be forced — CI uses this to prove the handoff path
runs). The chaos flags (``--fault-rate/--fault-kinds/--fault-seed/
--kill-after/--snapshot-every``) wrap the run in the two-phase identity
check above — the launcher-level version of docs/robustness.md's
acceptance criterion.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import numpy as np

from repro.configs.registry import (ARCHS, default_cache_backend, get_config,
                                    get_smoke)
from repro.cluster import (EngineFailedError, FaultInjector, FaultPlan,
                           MigrateOnOversubscription, MigrationFailedError,
                           Replica, RequestFailedError, Router)
from repro.engine import Request
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.launch.serve import build_engine


def parse_replicas(spec: str, smoke: bool, error) -> list:
    out = []
    for i, item in enumerate(spec.split(",")):
        item = item.strip()
        if not item:
            continue
        arch, _, cache = item.partition(":")
        cache = cache or "auto"
        if arch not in ARCHS:
            error(f"--replicas[{i}]: unknown arch {arch!r}")
        if cache not in ("auto", "paged", "slots", "recurrent"):
            error(f"--replicas[{i}]: unknown cache {cache!r}")
        cfg = get_smoke(arch) if smoke else get_config(arch)
        if cfg.is_encoder:
            error(f"--replicas[{i}]: {arch} is encoder-only")
        if cache == "auto":
            cache = default_cache_backend(cfg)
        out.append((arch, cache, cfg))
    if not out:
        error("--replicas is empty")
    return out


def build_replicas(specs, args) -> list:
    """One engine per ``(arch, cache, cfg)`` spec. Replica ``i`` gets its
    own one-device mesh on ``jax.devices()[i % n]``; replicas of one arch
    share one weight tree, which ``inject_params`` places onto each
    replica's device. Returns ``[(engine, arch), ...]``."""
    devices = jax.devices()
    engines = []
    params_by_arch: dict = {}
    for i, (arch, cache, cfg) in enumerate(specs):
        mesh = make_local_mesh((1, 1), devices=[devices[i % len(devices)]])
        per_seq = -(-args.max_len // args.block_size)
        with mesh:
            eng = build_engine(
                cfg, mesh, cache=cache, slots=args.slots,
                max_len=args.max_len,
                num_blocks=args.blocks or per_seq * args.slots,
                block_size=args.block_size, chunk=args.chunk,
                scheduler=args.scheduler, seed=args.seed,
                placement="auto", engine_id=f"{arch}:{cache}#{i}")
            # one weight tree per arch, injected into every replica of
            # that arch: the rFaaS lease model — N warm executors, one
            # shipped weight state
            eng.inject_params(params_by_arch.get(arch))
            params_by_arch.setdefault(arch, eng.params)
        engines.append((eng, arch))
    return engines


def run_phase(label, engines, specs, prompts, args, *,
              injector=None, snapshot_every=0):
    """Serve the fixed request set once on restarted engines behind a
    fresh router; returns (outputs per rid, failed rids, metrics, dt)."""
    for eng, _arch in engines:
        eng.restart()
    replicas = [Replica(eng, model=arch) for eng, arch in engines]
    rebalance = (MigrateOnOversubscription()
                 if args.rebalance == "oversubscription" else None)
    router = Router(replicas, rebalance=rebalance,
                    snapshot_every=snapshot_every,
                    retry_backoff_s=0.0 if injector else 0.001)
    if injector is not None:
        injector.install(router)

    handles = []
    for rid in range(args.requests):
        arch = specs[rid % len(specs)][0]
        handles.append(router.submit(
            Request(rid, prompts[rid], max_new_tokens=args.max_new,
                    priority=rid % 3), model=arch))

    t0 = time.perf_counter()
    forced = None
    ticks = 0
    while router.pending() and ticks < 10_000:
        router.tick()
        ticks += 1
        if (args.migrate_after and forced is None
                and ticks >= args.migrate_after):
            # force one live handoff: the first unfinished request
            # whose replica has a compatible live peer
            for h in handles:
                if h.done or router.request_failure(h.rid) is not None:
                    continue
                src = router._by_id[h.engine_id]
                if src.failed:
                    continue
                # prefer a peer with headroom, but force the handoff
                # onto any compatible replica — it queues there
                dst = (router.best_target(src)
                       or next(iter(router.compatible_targets(src)),
                               None))
                if dst is None:
                    continue
                try:
                    router.migrate(h.rid, dst.engine_id,
                                   reason="forced")
                except (MigrationFailedError, EngineFailedError):
                    continue        # rolled back / source died: retry
                forced = (h.rid, src.engine_id, dst.engine_id)
                break
    dt = time.perf_counter() - t0
    outputs, failed = {}, {}
    for h in handles:
        try:
            outputs[h.rid] = list(h.result().out_tokens)
        except RequestFailedError as err:
            failed[h.rid] = str(err)

    m = router.metrics()
    undrained = router.pending()
    total_tokens = sum(len(t) for t in outputs.values())
    print(f"[{label}] {len(outputs)}/{args.requests} requests over "
          f"{len(replicas)} replicas, {total_tokens} tokens in {dt:.2f}s "
          f"({total_tokens / max(dt, 1e-9):.1f} tok/s, {ticks} ticks)")
    for r in m["cluster"]["replicas"]:
        eng_m = m["replicas"][r["engine_id"]]
        print(f"  {r['engine_id']}: model={r['model']} cache={r['cache']} "
              f"completed={eng_m['completed']} "
              f"migrations={eng_m['migrations']} "
              f"failed={r['failed']} "
              f"placement={eng_m['engine']['placement']}")
    f = m["faults"]
    print(f"[{label}] migrations={m['totals']['migrations']} "
          f"(handoff: {m['router']['handoff_frames']} frames, "
          f"{m['router']['handoff_bytes']} bytes) "
          f"rebalance_events={m['router']['rebalance_events']}")
    if injector is not None:
        print(f"[{label}] faults: injected={f['injected']['injected']} "
              f"detected={f['detected']} retransmits={f['retransmits']} "
              f"failovers={f['failovers']} "
              f"recovered={f['requests_recovered']} "
              f"snapshots={f['snapshots_taken']}")
    if forced:
        rid, src, dst = forced
        print(f"[{label}] forced migration: rid {rid} {src} -> {dst}")
    return outputs, failed, m, forced, undrained


def make_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--replicas", required=True,
                   help="comma list of arch:cache replica specs, e.g. "
                        "llama3.2-1b:paged,llama3.2-1b:paged")
    p.add_argument("--smoke", action="store_true",
                   help="serve each arch's reduced CPU-test config instead "
                        "of its published widths")
    p.add_argument("--seed", type=int, default=0,
                   help="seed of the random weights and prompts")
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--prompt-len", type=int, default=12)
    p.add_argument("--max-new", type=int, default=8)
    p.add_argument("--slots", type=int, default=3)
    p.add_argument("--max-len", type=int, default=64)
    p.add_argument("--blocks", type=int, default=0,
                   help="paged replicas: pool blocks (0 => one max_len "
                        "sequence per slot)")
    p.add_argument("--block-size", type=int, default=8)
    p.add_argument("--chunk", type=int, default=4)
    p.add_argument("--scheduler", choices=("fifo", "priority", "sjf"),
                   default="fifo")
    p.add_argument("--rebalance", choices=("none", "oversubscription"),
                   default="oversubscription")
    p.add_argument("--migrate-after", type=int, default=0, metavar="N",
                   help="after N router ticks, force one live migration "
                        "of an in-flight request between compatible "
                        "replicas; exit 1 if none was possible")
    p.add_argument("--fault-rate", type=float, default=0.0,
                   help="per-frame fault probability on handoff trains; "
                        ">0 runs a noise-free baseline first and exits 1 "
                        "unless the chaos run matches it bitwise")
    p.add_argument("--fault-kinds", default="drop,corrupt,duplicate,reorder",
                   help="comma list of frame fault kinds to draw from")
    p.add_argument("--fault-seed", type=int, default=0)
    p.add_argument("--kill-after", type=int, default=0, metavar="N",
                   help="kill the first replica at router tick N of the "
                        "chaos phase (requires a compatible peer)")
    p.add_argument("--snapshot-every", type=int, default=2,
                   help="chaos phase: sequence-state snapshot cadence "
                        "(router ticks; 0 = recompute-only failover)")
    p.add_argument("--metrics-json", action="store_true",
                   help="print the final cluster metrics() as JSON")
    return p


def main() -> None:
    p = make_parser()
    args = p.parse_args()

    enable_compile_cache()
    specs = parse_replicas(args.replicas, args.smoke, p.error)
    engines = build_replicas(specs, args)

    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(0, specs[rid % len(specs)][2].vocab_size,
                            size=(args.prompt_len,)).astype(np.int32)
               for rid in range(args.requests)]

    chaos = args.fault_rate > 0 or args.kill_after > 0
    outputs, failed, m, forced, undrained = run_phase(
        "cluster" if not chaos else "baseline",
        engines, specs, prompts, args)
    ok = True
    if failed:
        print(f"[cluster] ERROR: requests failed without faults: {failed}",
              file=sys.stderr)
        ok = False

    if chaos and ok:
        plan = FaultPlan(
            seed=args.fault_seed, frame_fault_rate=args.fault_rate,
            fault_kinds=tuple(
                k.strip() for k in args.fault_kinds.split(",") if k.strip()),
            kill_at={engines[0][0].engine_id: args.kill_after}
            if args.kill_after else {})
        injector = FaultInjector(plan)
        c_out, c_failed, m, forced, undrained = run_phase(
            "chaos", engines, specs, prompts, args,
            injector=injector, snapshot_every=args.snapshot_every)
        if c_failed:
            print(f"[chaos] ERROR: requests terminally failed: {c_failed}",
                  file=sys.stderr)
            ok = False
        if undrained:
            print("[chaos] ERROR: cluster did not drain", file=sys.stderr)
            ok = False
        mismatched = [rid for rid in outputs
                      if c_out.get(rid) != outputs[rid]]
        if mismatched:
            print(f"[chaos] ERROR: outputs diverged from the noise-free "
                  f"baseline for rids {mismatched}", file=sys.stderr)
            ok = False
        if args.kill_after and m["faults"]["failovers"] == 0:
            print("[chaos] ERROR: --kill-after was set but no failover "
                  "happened", file=sys.stderr)
            ok = False
        if ok:
            print(f"[chaos] outputs bitwise identical to baseline across "
                  f"{len(outputs)} requests "
                  f"(injected={m['faults']['injected']['injected']}, "
                  f"recovered={m['faults']['requests_recovered']})")

    if args.metrics_json:
        print(json.dumps(m, default=str, indent=2))
    if args.migrate_after and m["totals"]["migrations"] == 0:
        print("[cluster] ERROR: --migrate-after was set but no migration "
              "happened (no compatible replica pair?)", file=sys.stderr)
        ok = False
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
