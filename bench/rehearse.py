#!/usr/bin/env python3
"""Compile every cell's device programs for a described TPU v5e, with no
chip attached, and print what each needs of the chip's memory.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [--topology v5e:2x2]

For each configuration in ``BENCHMARK.json``: the engine's prefill at
every prompt length its cells' traffic sends, the decode chunk at the
configuration's slot count and the slot write, over params at the
configuration's stated dtype as the served engine holds them, and the
reference's weight init and forward (the configuration's architecture
module) as a run's check calls them, each on one chip of the described
topology. A program the TPU compiler refuses fails here, and
one whose arguments and temporaries pass the chip's HBM is reported.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--topology", default="v5e:2x2")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from harness import spec, system
    root = HERE.parent
    system.program_on_path(root)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from harness import reference as R
    from repro.configs import get_config
    from repro.core.tokenizer import Tokenizer
    from repro.models import model as M
    from repro.serving.engine import Engine

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)
    one = SingleDeviceSharding(topo.devices[0])
    hbm = json.loads((HERE / "peaks.json").read_text())["devices"][
        "TPU v5 lite"]["hbm_bytes"]
    bench = spec.load_benchmark(root)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def place(tree):
        return jax.tree_util.tree_map(lambda s: sds(s.shape, s.dtype), tree)

    def report(name, compiled):
        m = compiled.memory_analysis()
        used = m.argument_size_in_bytes + m.temp_size_in_bytes
        print(f"  {name}: arguments {m.argument_size_in_bytes / 1e9:.3f} GB"
              f" + temporaries {m.temp_size_in_bytes / 1e9:.3f} GB"
              f" = {used / 1e9:.3f} GB of {hbm / 1e9:.0f} GB"
              f"{'' if used < hbm else '  DOES NOT FIT'}", flush=True)
        return used

    def plan(w, cfg):
        """The cell's requests at the benchmark's window, for seed 0."""
        import run
        cell = spec.load_cell(root, bench, w["name"])
        return run.build_traffic(cell, 0, bench["run_seconds"],
                                 run.knowledge_view(cfg))[0]

    worst = 0
    for entry in bench["configs"]:
        cfg = json.loads((root / entry["file"]).read_text())
        sv = cfg["serving"]
        lengths = sorted({r.prompt_len for w in bench["workloads"]
                          if w["config"] == entry["name"]
                          for r in plan(w, cfg) if r.kind == "miss"})
        arch = spec.load_arch(root, cfg)
        mcfg = get_config(system.register_model(cfg, arch))
        print(f"{entry['name']}: {arch.param_count(cfg) / 1e9:.3f} B "
              f"parameters, prompt lengths {lengths}, {sv['decode_slots']} "
              f"slots, max_len {sv['max_len']}", flush=True)
        eng = Engine(mcfg, None, Tokenizer(["x"]), max_len=sv["max_len"],
                     chunk=sv["chunk"])
        params = place(jax.eval_shape(lambda: M.init_model(
            jax.random.PRNGKey(0), mcfg,
            dtype=jnp.dtype(cfg["torch_dtype"]))))
        B = sv["decode_slots"]
        cache = place(M.cache_struct(mcfg, B, sv["max_len"]))
        for n in lengths:
            c = eng._prefill.lower(params, sds((1, n), jnp.int32)).compile()
            worst = max(worst, report(f"prefill (1, {n})", c))
        c = eng._decode_chunk.lower(
            params, sds((B, 1), jnp.int32), cache, sds((), jnp.int32),
            sds((2,), jnp.uint32), None, sds((B,), jnp.bool_)).compile()
        worst = max(worst, report(f"decode chunk ({B} slots)", c))
        one_cache = place(jax.eval_shape(
            lambda p, t: M.prefill(mcfg, p, {"tokens": t}, eng.run,
                                   max_len=sv["max_len"])[1],
            params, sds((1, lengths[0]), jnp.int32)))
        c = eng._write_slot.lower(cache, one_cache,
                                  sds((), jnp.int32)).compile()
        report("slot write", c)
        c = jax.jit(lambda s: arch.init_weights(cfg, s)).lower(
            sds((), jnp.int32)).compile()
        report("reference weights", c)
        w = place(jax.eval_shape(lambda: arch.init_weights(cfg, 0)))
        pad = sv["max_len"]
        with jax.default_matmul_precision("highest"):
            c = R._logits.lower(arch.forward, R._frozen(cfg), w,
                                sds((8, pad), jnp.int32), False).compile()
        report(f"reference forward (8, {pad})", c)
    print(f"largest serving program: {worst / 1e9:.3f} GB", flush=True)


if __name__ == "__main__":
    main()
