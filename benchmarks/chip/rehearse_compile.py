"""Compile a cell's serving programs for a described TPU v5e, without one.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse_compile.py <cell>

Lowers and compiles, for one chip of a described ``v5e:2x2``, what the
cell's window runs: the weights builder, the prefill at batch × each
prompt length (Pallas flash kernel compiled, not interpreted) and the
decode step at the cell's KV capacity, all in bfloat16. Prints each
program's ``memory_analysis()`` and the Pallas calls it holds. Nothing
runs, so it says nothing of time; it finds what the chip's compiler
would refuse (a program larger than HBM, a kernel off its tiling).
"""
from __future__ import annotations

import importlib
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

HBM_BYTES = 15.75 * 2**30  # one v5e chip's HBM, as its compiler counts


def _memory(compiled) -> dict:
    m = compiled.memory_analysis()
    return {
        "argument_bytes": m.argument_size_in_bytes,
        "output_bytes": m.output_size_in_bytes,
        "temp_bytes": m.temp_size_in_bytes,
        "alias_bytes": m.alias_size_in_bytes,
        "total_bytes": m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes - m.alias_size_in_bytes,
    }


def main(cell_name: str) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import cells
    import model
    from repro.models.transformer import (
        ApplyCtx,
        abstract_cache,
        abstract_model_params,
    )
    from repro.serving.engine import make_prefill_step, make_serve_step

    # a compile for a described chip cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    # the interpret default sees this CPU; the chip compiles the kernel
    fa = importlib.import_module("repro.kernels.flash_attention.flash_attention")
    fa.default_interpret = lambda: False

    cell = cells.resolve(cell_name)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    cfg = model.program_config(cell.config)
    rcfg = model.run_config(cell.config, use_pallas=True)
    ctx = ApplyCtx(cfg, rcfg, None)
    abstract = abstract_model_params(cfg, rcfg)
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), abstract)
    batch, cap = int(cell.traffic["batch"]), model.max_len(cell.traffic)
    report = {"cell": cell_name, "layers": cfg.n_layers,
              "params": int(sum(a.size for a in jax.tree.leaves(abstract)))}

    build = jax.jit(model.weight_builder(cell.config, abstract))
    programs = {"weights": build.lower(sds((2,), jnp.uint32)).compile()}
    for seq in sorted(set(int(p) for p in cell.traffic["prompt_lens"])):
        step = make_prefill_step(ctx, capacity=cap)
        programs[f"prefill_{batch}x{seq}"] = jax.jit(
            lambda p, t, step=step: step(p, {"tokens": t})
        ).lower(params, sds((batch, seq), jnp.int32)).compile()
    cache = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                         abstract_cache(cfg, batch, cap))
    programs[f"decode_{batch}x{cap}"] = jax.jit(make_serve_step(ctx)).lower(
        params, cache, sds((batch, 1), jnp.int32)).compile()

    ok = True
    for name, c in programs.items():
        mem = _memory(c)
        text = c.as_text()
        kernels = sorted({line.split(" = ")[0].strip() for line in text.splitlines()
                          if "tpu_custom_call" in line and " = " in line})
        report[name] = {**mem, "pallas_calls": kernels[:4]}
        ok &= mem["total_bytes"] <= HBM_BYTES
    print(json.dumps(report, indent=1, sort_keys=True))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
