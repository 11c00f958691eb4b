"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch olmoe-1b-7b --smoke \
      --steps 50 --batch 8 --seq 128

On this CPU container use ``--smoke`` (reduced config, 1 device). On a real
pod, omit it: the same driver builds the production mesh and shards the
full config (the launcher is identical — only the mesh differs).

Enables the XLA latency-hiding scheduler (compute/collective overlap) when
running on TPU — one of the distributed-optimization defaults of DESIGN §6.
"""
from __future__ import annotations

import argparse
import dataclasses
import os


def _tpu_overlap_flags() -> None:
    if "libtpu" in os.environ.get("TPU_LIBRARY_PATH", "") or \
            os.environ.get("JAX_PLATFORMS", "") == "tpu":
        os.environ["LIBTPU_INIT_ARGS"] = (
            os.environ.get("LIBTPU_INIT_ARGS", "")
            + " --xla_enable_async_collective_permute=true"
            + " --xla_tpu_enable_latency_hiding_scheduler=true")


_tpu_overlap_flags()

import jax  # noqa: E402

from repro import compat  # noqa: E402
from repro.configs.base import SHAPES, OptimizerConfig, RunConfig, ShardingConfig  # noqa: E402
from repro.configs.registry import ARCHS, get_config, get_smoke  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.runtime.trainer import Trainer, TrainerConfig  # noqa: E402


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", choices=sorted(ARCHS), required=True)
    p.add_argument("--shape", choices=sorted(SHAPES), default="train_4k")
    p.add_argument("--smoke", action="store_true",
                   help="reduced config + 1-device mesh (CPU)")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=None,
                   help="global batch override")
    p.add_argument("--seq", type=int, default=None, help="seq-len override")
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--checkpoint-dir", default="/tmp/repro_ckpt")
    p.add_argument("--checkpoint-every", type=int, default=50)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--transport", default=None,
                   choices=("local", "injected", "auto"),
                   help="MoE jam transport override")
    args = p.parse_args()
    enable_compile_cache()

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    if args.transport and cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, transport=args.transport))
    shape = SHAPES[args.shape]
    if args.seq:
        shape = dataclasses.replace(shape, seq_len=args.seq)
    if args.batch:
        shape = dataclasses.replace(shape, global_batch=args.batch)

    if args.smoke:
        mesh = compat.make_mesh((1, 1), ("data", "model"))
        sharding = ShardingConfig(dp_axes=("data",), fsdp_params=False)
    else:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        sharding = ShardingConfig(
            dp_axes=("pod", "data") if args.multi_pod else ("data",))

    run = RunConfig(
        model=cfg, shape=shape, sharding=sharding,
        optimizer=OptimizerConfig(lr=args.lr, total_steps=args.steps,
                                  warmup_steps=max(1, args.steps // 10)),
        checkpoint_dir=args.checkpoint_dir)
    tcfg = TrainerConfig(steps=args.steps, log_every=args.log_every,
                         checkpoint_every=args.checkpoint_every)

    with mesh:
        trainer = Trainer(cfg, run, mesh, tcfg=tcfg)
        stats = trainer.train()
    print(f"[train] done: {stats.steps} steps, "
          f"loss={stats.final_metrics.get('loss', float('nan')):.4f}, "
          f"p50={stats.p50_s*1e3:.1f}ms p99.9={stats.p999_s*1e3:.1f}ms "
          f"restarts={stats.restarts}")


if __name__ == "__main__":
    main()
