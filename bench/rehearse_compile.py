"""Compile each cell's programs at full width for a described TPU v5e.

  JAX_PLATFORMS=cpu python bench/rehearse_compile.py [cell ...]

No chip is needed: the TPU compiler compiles for a v5e that is described,
not attached. For each cell it compiles the paged step as the engine builds
it (and checks that the Mosaic kernel is in it), the seeded weight
generator and the reference's layer, and prints their
``memory_analysis()``, from which the pool sizes in the configurations
were set. Run it after a change to a configuration, a mix or the program.
"""
from __future__ import annotations

import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))


def mem(compiled) -> dict:
    m = compiled.memory_analysis()
    return {k: getattr(m, k + "_size_in_bytes") for k in
            ("argument", "output", "temp", "generated_code")}


def main() -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import check
    import harness
    import spec
    from repro.kernels.paged_attention import ops

    ops._on_tpu = lambda: True          # compile Mosaic, do not interpret
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    bench = spec.read_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    names = sys.argv[1:] or [w["name"] for w in bench["workloads"]]
    for name in names:
        cell = spec.load_cell(name)
        g = cell.geometry
        W, reference = cell.block("weights"), cell.block("reference")
        out = {"cell": name, "slots": cell.slots, "max_len": cell.max_len,
               "num_blocks": cell.num_blocks()}
        t = time.perf_counter()
        server = harness.Server(cell, on_tpu=False, devices=topo.devices[:1])
        step = server.engine.lower_step().compile()
        out["step_compile_s"] = round(time.perf_counter() - t, 1)
        out["step_kernels"] = len(check.custom_call_names(step.as_text()))
        out["step"] = mem(step)
        scalar = jax.ShapeDtypeStruct((), jnp.uint32, sharding=chip)
        gen = jax.jit(lambda lo, hi: W.make_params(g, lo, hi),
                      out_shardings=server.engine.pshard)
        out["weights"] = mem(gen.lower(scalar, scalar).compile())
        P = -(-cell.max_len // 128) * 128
        x = jax.ShapeDtypeStruct((check.SAMPLE, P, g.d_model), jnp.float32,
                                 sharding=chip)
        out["reference_layer"] = mem(reference.layer.lower(
            g, x, scalar, scalar, scalar, None).compile())
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
