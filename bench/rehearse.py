#!/usr/bin/env python3
"""Compile each cell's step programs for a described TPU v5e, without a chip,
and print what the compiler says they hold in device memory.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [--workload <cell>]

For each configuration this builds the served engine as the harness does
(its own jitted paged step), with the weights and page pool given as shapes
placed on device 0 of a described ``v5e:2x2``, and compiles the step at
every prefill chunk size and the decode shape; then the weight draw and the
float32 reference at its largest sequence bucket.  A program the TPU's
compiler refuses, or one that does not fit, fails here.  Nothing runs.
"""
import argparse
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import harness, spec, weights
    from bench.reference import model as ref
    from repro.serving import engine as engine_mod

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev0 = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=dev0), tree)

    # the engine picks interpret mode from the host's backend; this build is
    # for the chip, so it takes the compiled kernel
    engine_mod.default_interpret = lambda: False
    done = set()
    for name in args.workload or spec.list_cells(ROOT):
        cell = spec.load_cell(name, ROOT)
        if cell.config_name in done:
            continue
        done.add(cell.config_name)
        cfg = harness.program_config(cell)
        a = ref.arch(cell.config)
        harness.check_config(cfg, a)
        ec = cell.config["engine"]
        kd = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=dev0)
        params = jax.eval_shape(lambda k: weights._program_draw(a, k), kd)
        eng = engine_mod.Engine(cfg, params, n_slots=int(ec["slots"]),
                                max_seq_len=int(ec["max_seq_len"]),
                                page_size=int(ec["page_size"]),
                                use_paged_kernel=True)
        cache = on_chip(jax.eval_shape(lambda: eng.cache))
        eng.cache = None
        p_sds = on_chip(params)
        B, pps = eng.n_slots, eng._ptab.shape[1]
        print(f"== {cell.config_name}: slots={B} max_seq_len="
              f"{eng.max_seq_len} pages={eng.page_pool.n_pages} "
              f"chunks={eng._chunk_sizes}", flush=True)
        for c in eng._chunk_sizes:
            i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=dev0)
            act = jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=dev0)
            comp = eng._paged_exec.lower(p_sds, cache, i32(B, c), i32(B, c),
                                         i32(B, pps), act).compile()
            m = comp.memory_analysis()
            kernel = "tpu_custom_call" in comp.as_text()
            print(f"  step ({B}, {c}): args={m.argument_size_in_bytes} "
                  f"out={m.output_size_in_bytes} temp={m.temp_size_in_bytes} "
                  f"alias={m.alias_size_in_bytes} kernel={kernel}", flush=True)
        comp = jax.jit(weights._draw, static_argnums=0).lower(a, kd).compile()
        m = comp.memory_analysis()
        print(f"  reference weights: out={m.output_size_in_bytes} "
              f"temp={m.temp_size_in_bytes}", flush=True)
        w = on_chip(jax.eval_shape(lambda k: weights._draw(a, k), kd))
        S = harness.bucket(int(ec["max_seq_len"]))
        t = jax.ShapeDtypeStruct((S,), jnp.int32, sharding=dev0)
        comp = ref.scores.lower(w, t, t, a=a, mode="f32").compile()
        m = comp.memory_analysis()
        print(f"  reference scores S={S}: args={m.argument_size_in_bytes} "
              f"temp={m.temp_size_in_bytes}", flush=True)
        del eng
    return 0


if __name__ == "__main__":
    sys.exit(main())
