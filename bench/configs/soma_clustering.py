"""Soma clustering (dissertation sec 4.7.1, Fig 4.18) as the quickstart
declares it: two cell types, each secreting its own substance and moving up
its gradient, with contact mechanics through the fused cell-list kernel.

The declaration is a copy of ``examples/quickstart.py::build_model`` (the
example may change; the benchmark's model may not), with every size read from
``soma_clustering.json`` and an int32 ``tag`` per agent for the check.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro import Simulation
from repro.core import ForceParams, chemotaxis, concentration_at, secretion


def exposure_op(ctx, state):
    """Integrate each agent's own-substance concentration (the quickstart's
    custom post op)."""
    pool = state.pool
    c0 = concentration_at(state.grids["substance_0"], pool.position)
    c1 = concentration_at(state.grids["substance_1"], pool.position)
    own = jnp.where(pool.kind == 0, c0, c1)
    dose = jnp.where(pool.alive, own * ctx.config.dt, 0.0)
    return dataclasses.replace(
        state, pool=pool.set_attr("exposure", pool.get("exposure") + dose)
    )


def kinds(cfg: dict, key, n: int):
    """Type 1 for exactly ``kind_share`` of the agents, type 0 for the rest,
    at slots drawn from ``key``."""
    count = round(cfg["kind_share"] * n)
    return (jax.random.permutation(key, n) < count).astype(jnp.int32)


def build(cfg: dict, agents: dict, seed: int) -> Simulation:
    """The model over ``agents`` (position, kind, tag) made by the traffic
    generator."""
    n = agents["position"].shape[0]
    # ``exposure`` starts as a float32 array, not the example's scalar 0.0: a
    # scalar is weakly typed, the step's output is not, and the second
    # ``run_jit`` call of a chunked run would trace and compile again.
    sim = (
        Simulation(space=tuple(cfg["space"]), cell_size=cfg["cell_size"],
                   boundary=cfg["boundary"], dt=cfg["dt"],
                   max_per_cell=cfg["max_per_cell"], seed=seed,
                   sort_frequency=cfg["sort_frequency"])
        .add_agents(n, position=agents["position"], diameter=cfg["diameter"],
                    kind=agents["kind"], exposure=jnp.zeros(n, jnp.float32),
                    tag=agents["tag"])
    )
    for s in cfg["substances"]:
        sim = sim.add_substance(s["name"], diffusion=s["diffusion"],
                                decay=s["decay"], resolution=s["resolution"])
    sim = sim.use(
        *[secretion(s["name"], s["secretion"], kind=s["kind"])
          for s in cfg["substances"]],
        *[chemotaxis(s["name"], s["chemotaxis"], kind=s["kind"])
          for s in cfg["substances"]],
    )
    return (
        sim.mechanics(ForceParams(**cfg["force"]), impl=cfg["impl"])
        .op(exposure_op, name="exposure", phase="post")
    )
