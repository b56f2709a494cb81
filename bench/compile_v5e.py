"""Compile a cell's window chunk at its real size for a described v5e chip
(no chip attached) and print the compiler's memory analysis.

    JAX_PLATFORMS=cpu python bench/compile_v5e.py --workload <cell>

This finds what the chip's compiler refuses (shapes, kernels, memory) at no
chip time.  It times nothing.  Pallas kernels are lowered for Mosaic by
making the backend read as ``tpu`` while the chunk is lowered.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from unittest import mock

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import run as harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--agents", type=int, default=None)
    args = ap.parse_args(argv)

    cell = harness.Cell.load(args.workload, held=True)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import population
    from repro.core import engine

    cfg = cell.cfg if args.agents is None else population.resized(
        cell.cfg, args.agents)
    built = cell.builder.build(
        cfg, population.agents(cfg, cell.traffic, 0, cell.builder.kinds), 0).build()
    built = dataclasses.replace(
        built, scheduler=harness.scoped_scheduler(built.scheduler))
    state = dataclasses.replace(
        built.state, step=jnp.asarray(cell.params["start_step"], jnp.int32))

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), state)
    fn = engine.jitted_runner(built.config, built.scheduler)
    t0 = time.perf_counter()
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        compiled = fn.lower(shapes, n_steps=cell.params["chunk_steps"],
                            observables=None).compile()
    m = compiled.memory_analysis()
    out = {"workload": cell.name, "agents": cfg["agents"],
           "compile_s": time.perf_counter() - t0,
           "temp_bytes": m.temp_size_in_bytes,
           "argument_bytes": m.argument_size_in_bytes,
           "output_bytes": m.output_size_in_bytes,
           "generated_code_bytes": m.generated_code_size_in_bytes,
           "tpu_custom_calls": compiled.as_text().count("tpu_custom_call")}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
