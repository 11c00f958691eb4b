"""Serving launcher: the unified engine with pluggable schedulers.

Serves on a mesh built from the devices present (``--mesh``, 1x1 by
default). ``--smoke`` swaps in the arch's reduced CPU-test config; without
it the published config serves, e.g. llama3.2-1b at full width on one TPU:

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
      --cache paged --slots 8 --max-len 544 --prompt-len 256 --max-new 32

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --requests 12 --slots 4 --max-new 16

  # paged backend (block-pool KV cache + chunked prefill):
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --cache paged --slots 12 --blocks 48 --block-size 8 --chunk 8

  # recurrent backend (constant-size SSM/xLSTM state, exact batching):
  PYTHONPATH=src python -m repro.launch.serve --arch mamba-130m --smoke \
      --cache recurrent --slots 4 --chunk 8

  # priority scheduling + per-token streaming:
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --cache paged --scheduler priority --stream --requests 4

  # multi-device paged serving (the shard_map'd Pallas kernel):
  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --cache paged --mesh 2x2 --paged-kernel pallas --chunk 4
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Optional, Tuple

import jax
import numpy as np

from repro.configs.base import SHAPES, ModelConfig, RunConfig, ShardingConfig
from repro.configs.registry import (ARCHS, default_cache_backend, get_config,
                                    get_smoke)
from repro.engine import Engine, Request
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh

DEFAULT_CHUNK = 8       # paged/recurrent prefill tokens per request per tick

# serving keeps whole weights per tensor shard (no FSDP) and the paged pool
# shards kv heads over the tensor axis, never the sequence
SERVE_SHARDING = ShardingConfig(fsdp_params=False, seq_axis=None)


def parse_mesh(spec: Optional[str]) -> Tuple[int, int]:
    """``"DPxTP"`` (e.g. ``"2x2"``) -> (dp, tp); None -> (1, 1)."""
    if not spec:
        return 1, 1
    try:
        dp, tp = (int(t) for t in spec.lower().split("x"))
    except ValueError:
        raise ValueError(f"--mesh wants DPxTP (e.g. 2x2), got {spec!r}") from None
    return dp, tp


def build_engine(cfg: ModelConfig, mesh: jax.sharding.Mesh, *, cache: str,
                 slots: int, max_len: int, num_blocks: int = 0,
                 block_size: int = 16, chunk: int = DEFAULT_CHUNK,
                 scheduler="fifo", kernel: str = "auto", seed: int = 0,
                 engine_id: Optional[str] = None,
                 placement: str = "local") -> Engine:
    """One serving ``Engine`` on ``mesh`` — the construction shared by
    the launchers and ``chip_smoke.py``. ``num_blocks=0`` sizes the paged
    pool at half the contiguous budget, floored at one ``max_len``
    sequence (the engine rejects anything smaller). Weights are not
    installed: call ``load_params``/``inject_params``."""
    run = RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                    sharding=SERVE_SHARDING, seed=seed)
    kw = dict(slots=slots, max_len=max_len, scheduler=scheduler,
              engine_id=engine_id, placement=placement)
    if cache == "paged":
        max_blocks_per_seq = -(-max_len // block_size)
        kw.update(num_blocks=num_blocks or max(
                      max_blocks_per_seq, (slots * max_len // 2) // block_size),
                  block_size=block_size, chunk=chunk, kernel=kernel)
    elif cache == "recurrent":
        kw.update(chunk=chunk)
    return Engine(cfg, run, mesh, cache=cache, **kw)


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", choices=sorted(ARCHS), required=True)
    p.add_argument("--smoke", action="store_true",
                   help="serve the arch's reduced CPU-test config instead "
                        "of its published widths")
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--max-len", type=int, default=256)
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--prompt-len", type=int, default=16)
    p.add_argument("--max-new", type=int, default=16)
    p.add_argument("--cache", choices=("auto", "paged", "slots", "recurrent"),
                   default=None,
                   help="sequence-state backend: paged (block pool), slots "
                        "(fixed-slot contiguous), recurrent (constant-size "
                        "SSM/xLSTM state), or auto (the model family's "
                        "default). Default: slots, or paged with --paged")
    p.add_argument("--paged", action="store_true",
                   help="alias for --cache paged (kept for scripts)")
    p.add_argument("--scheduler", choices=("fifo", "priority", "sjf"),
                   default="fifo",
                   help="scheduler policy: fifo (submission order), "
                        "priority (Request.priority-aware; requests here "
                        "get priority rid %% 3 so reordering is visible), "
                        "sjf (shortest prompt first)")
    p.add_argument("--stream", action="store_true",
                   help="consume per-request token streams "
                        "(handle.tokens()) instead of run_until_drained")
    p.add_argument("--blocks", type=int, default=0,
                   help="paged: pool size in blocks (0 => slots*max_len/2 "
                        "worth of tokens — half the contiguous budget)")
    p.add_argument("--block-size", type=int, default=16,
                   help="paged: tokens per block")
    p.add_argument("--chunk", type=int, default=DEFAULT_CHUNK,
                   help="paged: prefill tokens per request per tick")
    p.add_argument("--paged-kernel", choices=("auto", "pallas", "ref"),
                   default="auto",
                   help="paged attention path: the stash-resident Pallas "
                        "block-table kernel (single- or multi-device — it "
                        "lowers through shard_map on meshes; interpreted "
                        "off-TPU), the gather-then-dense reference, or "
                        "auto (pallas)")
    p.add_argument("--mesh", default=None, metavar="DPxTP",
                   help="mesh shape over the devices present, e.g. 2x2 or "
                        "1x4 (axes data x model; needs dp*tp devices — on "
                        "CPU set XLA_FLAGS=--xla_force_host_platform_device_"
                        "count=N). Default: 1x1")
    p.add_argument("--metrics-json", action="store_true",
                   help="print the final Engine.metrics() dict as JSON")
    args = p.parse_args()

    enable_compile_cache()
    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    if cfg.is_encoder:
        raise SystemExit(f"{args.arch} is encoder-only: no decode path")

    # resolve the backend, then refuse incoherent flag combinations instead
    # of silently ignoring them (a --paged-kernel that never engages looks
    # like a benchmark of the kernel while benchmarking the dense path)
    if args.paged and args.cache not in (None, "paged"):
        p.error(f"--paged conflicts with --cache {args.cache}")
    cache = args.cache or ("paged" if args.paged else "slots")
    if cache == "auto":
        cache = default_cache_backend(cfg)
        print(f"[serve] --cache auto -> {cache!r} for {args.arch}")
    if args.paged_kernel != "auto" and cache != "paged":
        p.error(f"--paged-kernel {args.paged_kernel} has no effect with "
                f"--cache {cache}; drop it or use --cache paged")
    if cache not in ("paged",) and (args.blocks or args.block_size != 16):
        p.error(f"--blocks/--block-size configure the paged pool and have "
                f"no effect with --cache {cache}")
    try:
        dp, tp = parse_mesh(args.mesh)
    except ValueError as e:
        p.error(str(e))
    if dp * tp > len(jax.devices()):
        p.error(f"--mesh {args.mesh} needs {dp * tp} devices, have "
                f"{len(jax.devices())} (on CPU: XLA_FLAGS="
                f"--xla_force_host_platform_device_count={dp * tp})")
    mesh = make_local_mesh((dp, tp))
    print(f"[serve] {cfg.name} on a {dp}x{tp} mesh of "
          f"{jax.devices()[0].platform} devices")

    rng = np.random.default_rng(0)
    with mesh:
        engine = build_engine(
            cfg, mesh, cache=cache, slots=args.slots, max_len=args.max_len,
            num_blocks=args.blocks, block_size=args.block_size,
            chunk=args.chunk, scheduler=args.scheduler,
            kernel=args.paged_kernel)
        engine.load_params()
        t0 = time.perf_counter()
        handles = []
        for rid in range(args.requests):
            prompt = rng.integers(
                0, cfg.vocab_size, size=(args.prompt_len,)).astype(np.int32)
            # a visible priority spread so --scheduler priority demonstrably
            # reorders admission (higher = more urgent)
            handles.append(engine.submit(
                Request(rid, prompt, max_new_tokens=args.max_new,
                        priority=rid % 3)))
        if args.stream:
            # pull each handle's stream; pulling one drives the engine, so
            # co-scheduled requests' tokens are found already buffered
            for h in handles:
                toks = []
                for tok in h.tokens():
                    toks.append(tok)
                print(f"[stream] req {h.rid} (prio {h.req.priority}): "
                      f"{toks[:8]}{'...' if len(toks) > 8 else ''}")
            done = engine.completed
        else:
            done = engine.run_until_drained()
        dt = time.perf_counter() - t0

    total_tokens = sum(len(r.out_tokens) for r in done)
    kind = cache
    m = engine.metrics()
    print(f"[serve:{kind}/{args.scheduler}] {len(done)}/{args.requests} "
          f"requests, {total_tokens} tokens in {dt:.2f}s "
          f"({total_tokens/dt:.1f} tok/s, {engine.ticks} ticks)")
    print(f"[serve:{kind}] admission order: {engine.admission_log}")
    if cache == "paged":
        print(f"[serve:paged] attention kernel={m['paged_kernel']} "
              f"live-token fraction last={m['live_token_fraction']:.3f} "
              f"mean={m['live_token_fraction_mean']:.3f}")
    elif cache == "recurrent":
        print(f"[serve:recurrent] state bytes/slot="
              f"{m['state_bytes_per_slot']} snapshots "
              f"taken={m['snapshots_taken']} "
              f"restored={m['snapshots_restored']}")
    for r in done[:3]:
        print(f"  req {r.rid}: {r.out_tokens[:8]}...")
    if engine.fabric is not None:
        fm = m["fabric"]
        print(f"[serve:{kind}] fabric '{fm['fabric']}': calls={fm['calls']} "
              f"placements={fm['placements']} "
              f"decisions={len(fm['decisions'])} leases={list(fm['leases'])}")
    if args.metrics_json:
        print(json.dumps(m, default=str, indent=2))


if __name__ == "__main__":
    main()
