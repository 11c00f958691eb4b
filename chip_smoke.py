"""Chip smoke: serve llama3.2-1b at its published widths on TPU v5e.

  python chip_smoke.py              # one chip
  python chip_smoke.py --chips 4    # four chips: Router over four replicas

One chip: builds the paged ``Engine`` through the serving launcher's own
construction code (``repro.launch.serve.build_engine``) with random weights
from ``--seed``, checks that ``kernel="auto"`` resolved to the Pallas kernel
and that the compiled step holds it as a Mosaic ``tpu_custom_call`` (not
interpret mode), serves 8 requests of 128-512 prompt tokens and 32 new
tokens through ``submit``/``run_until_drained``, checks every served token
against the same step's logits fed the same tokens
(``repro.engine.teacher_forcing``), and compares those prefill and decode
logits of two requests with a plain float32 forward
(``repro.models.reference``).

Four chips: one process drives four full-width replicas, one per chip,
behind the ``Router`` (``repro.launch.serve_cluster``'s own construction
code), forces a live migration across chips, and checks every request's
tokens against a one-replica run of the same requests, and that each
replica's weights and KV pool live on its own chip only.

Timings printed here are from one smoke run, not benchmark metrics. Any
failed check raises, so the exit code is non-zero and the last line is not
printed. Finding no TPU is a failure: nothing falls back to the CPU.
The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "llama3.2-1b"
SLOTS = 8
BLOCK_SIZE = 16
PROMPT_LENS = (128, 512)          # prompt lengths drawn from [lo, hi]
MAX_NEW = 32
MAX_LEN = PROMPT_LENS[1] + MAX_NEW
N_REQUESTS = 8
N_COMPARED = 2

# Served (bf16 weights and activations, paged Pallas kernel) vs the float32
# reference on the same bf16-rounded weights. At smaller widths on the CPU
# bf16 serving lands at an RMS error of 1-2% of the reference logits' RMS
# and a largest error of 4-8% of their standard deviation; a masking,
# layout or position fault moves logits by their own scale (errors near
# 100%). The bounds sit between the two.
RMS_TOL = 0.05                    # rms(served - ref) / rms(ref)
MAX_TOL = 0.25                    # max|served - ref| / std(ref)
# Served token vs the teacher-forced bf16 step: (max logit - logit of the
# served token) / std of that position's logits. Two compilations of the
# same bf16 step may round a near-tie either way; a wrong token lands far
# below the max (the top two of ~128k random-weight logits sit about 0.2
# std apart on average).
TIE_TOL = 0.05


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require_tpu(count: int) -> None:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX found "
                         f"{devices[0].platform} devices only")
    if len(devices) < count:
        raise SystemExit(f"chip_smoke --chips {count} needs {count} TPU "
                         f"chips, JAX found {len(devices)}")


def make_prompts(rng, n: int, vocab: int) -> list:
    lo, hi = PROMPT_LENS
    return [rng.integers(0, vocab, size=(int(rng.integers(lo, hi + 1)),))
            .astype(np.int32) for _ in range(n)]


def placed_on(engine, device) -> bool:
    leaves = jax.tree.leaves(engine.params) + jax.tree.leaves(engine.cache)
    return all(leaf.devices() == {device} for leaf in leaves)


def check_compiled_kernel(engine) -> float:
    """Assert the step runs the Pallas kernel compiled by Mosaic, not
    interpreted; returns the step's compile seconds."""
    assert engine.paged_kernel == "pallas", engine.paged_kernel
    t0 = time.perf_counter()
    text = engine.lower_step().compile().as_text()
    assert "tpu_custom_call" in text, "the compiled step holds no Mosaic kernel"
    return time.perf_counter() - t0


def one_chip(seed: int) -> None:
    from repro.configs.registry import get_config
    from repro.engine import Request
    from repro.engine.teacher_forcing import teacher_forced_logits
    from repro.launch.mesh import make_local_mesh
    from repro.launch.serve import DEFAULT_CHUNK, build_engine
    from repro.models import reference

    cfg = get_config(ARCH)
    mesh = make_local_mesh((1, 1))
    with mesh:
        engine = build_engine(cfg, mesh, cache="paged", slots=SLOTS,
                              max_len=MAX_LEN, block_size=BLOCK_SIZE,
                              chunk=DEFAULT_CHUNK, kernel="auto", seed=seed)
        compile_s = check_compiled_kernel(engine)
        log(f"{cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
            f"{cfg.attention.num_heads} heads over "
            f"{cfg.attention.num_kv_heads} kv heads of "
            f"{cfg.attention.head_dim}, d_ff {cfg.d_ff}, vocab "
            f"{cfg.vocab_size}; pool {engine.num_blocks} blocks of "
            f"{BLOCK_SIZE}, {SLOTS} slots, chunk {DEFAULT_CHUNK}")
        log(f"kernel=pallas, compiled step holds tpu_custom_call; "
            f"step compile {compile_s:.1f} s")

        t0 = time.perf_counter()
        engine.load_params()
        jax.block_until_ready(engine.params)
        log(f"random weights from seed {seed} in "
            f"{time.perf_counter() - t0:.1f} s")

        rng = np.random.default_rng(seed)
        prompts = make_prompts(rng, N_REQUESTS, cfg.vocab_size)
        t0 = time.perf_counter()
        for rid, prompt in enumerate(prompts):
            engine.submit(Request(rid, prompt, max_new_tokens=MAX_NEW))
        done = {r.rid: r for r in engine.run_until_drained()}
        serve_s = time.perf_counter() - t0
        assert sorted(done) == list(range(N_REQUESTS)), sorted(done)
        assert all(len(r.out_tokens) == MAX_NEW for r in done.values())
        m = engine.metrics()
        peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
        log(f"smoke run (first call compiles; not a benchmark): "
            f"{N_REQUESTS}/{N_REQUESTS} requests complete, prompts "
            f"{sorted(len(p) for p in prompts)}, {N_REQUESTS * MAX_NEW} new "
            f"tokens in {serve_s:.2f} s over {engine.ticks} ticks, "
            f"preemptions {m['preemptions']}, peak_bytes_in_use {peak}")

        # every served token against the logits of the same paged step fed
        # the same tokens (teacher forcing): a token is right if it is the
        # argmax there, or within TIE_TOL of it where two logits near-tie
        seqs = [[int(t) for t in p] + done[i].out_tokens[:-1]
                for i, p in enumerate(prompts)]
        forced = teacher_forced_logits(engine, seqs,
                                       [len(p) for p in prompts])
        exact, worst_gap = 0, 0.0
        for i, p in enumerate(prompts):
            rows = forced[i][len(p) - 1:]
            picked = rows[np.arange(MAX_NEW), done[i].out_tokens]
            gap = (rows.max(-1) - picked) / rows.std(-1)
            exact += int((gap == 0).sum())
            worst_gap = max(worst_gap, float(gap.max()))
        log(f"served tokens vs the teacher-forced step: {exact}/"
            f"{N_REQUESTS * MAX_NEW} are its argmax; largest gap to the "
            f"max logit {worst_gap:.4f} std (tol {TIE_TOL})")
        assert worst_gap <= TIE_TOL, worst_gap

        # the first requests' logits vs the float32 reference
        ref_fn = jax.jit(lambda p, x: reference.forward_f32(cfg, p, x))
        for i in range(N_COMPARED):
            seq = seqs[i]
            padded = np.zeros((1, MAX_LEN), np.int32)   # causal: pad is inert
            padded[0, :len(seq)] = seq
            ref = np.asarray(ref_fn(engine.params, jnp.asarray(padded))[0,
                                                                   :len(seq)])
            diff = forced[i] - ref
            rms = float(np.sqrt(np.mean(diff ** 2) / np.mean(ref ** 2)))
            worst = float(np.abs(diff).max() / ref.std())
            L = len(prompts[i])
            agree = int((ref[L - 1:].argmax(-1)
                         == np.asarray(done[i].out_tokens)).sum())
            log(f"request {i}: {len(seq)} positions ({L} prefill + "
                f"{len(seq) - L} decode) vs float32 reference: rms rel "
                f"{rms:.4f} (tol {RMS_TOL}), max|diff|/std {worst:.4f} "
                f"(tol {MAX_TOL}); served greedy tokens agree with the "
                f"reference argmax at {agree}/{MAX_NEW} steps")
            assert rms <= RMS_TOL and worst <= MAX_TOL, (i, rms, worst)


def four_chips(seed: int) -> None:
    from repro.launch import serve_cluster

    devices = jax.devices()[:4]
    args = serve_cluster.make_parser().parse_args([
        "--replicas", ",".join([f"{ARCH}:paged"] * 4),
        "--requests", str(N_REQUESTS), "--max-new", str(MAX_NEW),
        "--slots", "4", "--max-len", str(MAX_LEN),
        "--block-size", str(BLOCK_SIZE), "--migrate-after", "3",
        "--seed", str(seed)])

    def error(msg):
        raise SystemExit(msg)

    specs = serve_cluster.parse_replicas(args.replicas, args.smoke, error)
    t0 = time.perf_counter()
    engines = serve_cluster.build_replicas(specs, args)
    log(f"4 replicas of {ARCH} built in {time.perf_counter() - t0:.1f} s")
    for (eng, _), dev in zip(engines, devices):
        check_compiled_kernel(eng)
        assert placed_on(eng, dev), f"{eng.engine_id} is not on {dev} alone"
    rng = np.random.default_rng(seed)
    prompts = make_prompts(rng, N_REQUESTS, specs[0][2].vocab_size)

    one_args = copy.copy(args)
    one_args.migrate_after = 0
    ref_out, ref_failed, _, _, _ = serve_cluster.run_phase(
        "one-replica", engines[:1], specs[:1], prompts, one_args)
    out, failed, m, forced, undrained = serve_cluster.run_phase(
        "four-replica", engines, specs, prompts, args)
    assert not ref_failed and not failed and not undrained, (ref_failed,
                                                             failed)
    assert len(out) == N_REQUESTS and out == ref_out, \
        [rid for rid in ref_out if out.get(rid) != ref_out[rid]]
    assert forced is not None and m["totals"]["migrations"] >= 1
    by_id = {eng.engine_id: eng for eng, _ in engines}
    src, dst = (by_id[forced[1]].mesh.devices.flat[0],
                by_id[forced[2]].mesh.devices.flat[0])
    assert src != dst, (src, dst)
    for (eng, _), dev in zip(engines, devices):
        assert placed_on(eng, dev), f"{eng.engine_id} left {dev}"
    log(f"tokens of all {N_REQUESTS} requests identical to the one-replica "
        f"run; {m['totals']['migrations']} migrations, forced rid "
        f"{forced[0]} from {src} to {dst}; every replica's weights and KV "
        f"pool on its own chip")


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    require_tpu(args.chips)
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        raise SystemExit(f"chip_smoke runs from the root of the repository "
                         f"checkout: {e}")
    enable_compile_cache()
    t0 = time.perf_counter()
    (one_chip if args.chips == 1 else four_chips)(args.seed)
    log(f"done in {time.perf_counter() - t0:.1f} s")
    devices = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
