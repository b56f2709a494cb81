"""Measles SIR (dissertation sec 4.6.3, Table 4.3) as
``examples/epidemiology_sir.py::run_abm`` declares it: random movement,
infection within a radius, recovery with a fixed probability per step, in a
toroidal space.

A copy of the example's declaration (the example may change; the benchmark's
model may not), with every size read from ``sir_measles.json`` and an int32
``tag`` per agent for the check.  The example's kind-count observable is left
out: it records a row per step on the host side of the scan and is not part
of the step.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro import Simulation
from repro.core import INFECTED, random_movement, sir_infection, sir_recovery


def infectious_time_op(ctx, state):
    """Accumulate each agent's time spent infected (the example's custom
    post op)."""
    pool = state.pool
    dt = jnp.where(pool.alive & (pool.kind == INFECTED), ctx.config.dt, 0.0)
    return dataclasses.replace(
        state, pool=pool.set_attr("t_inf", pool.get("t_inf") + dt)
    )


def kinds(cfg: dict, key, n: int):
    """Infected for exactly ``infected_share`` of the agents, susceptible for
    the rest, at slots drawn from ``key``."""
    count = round(cfg["infected_share"] * n)
    infected = jax.random.permutation(key, n) < count
    return jnp.where(infected, INFECTED, 0).astype(jnp.int32)


def build(cfg: dict, agents: dict, seed: int) -> Simulation:
    n = agents["position"].shape[0]
    # ``t_inf`` starts as a float32 array, not the example's scalar 0.0: a
    # scalar is weakly typed, the step's output is not, and the second
    # ``run_jit`` call of a chunked run would trace and compile again.
    return (
        Simulation(space=tuple(cfg["space"]), cell_size=cfg["cell_size"],
                   boundary=cfg["boundary"], dt=cfg["dt"],
                   max_per_cell=cfg["max_per_cell"], seed=seed,
                   sort_frequency=cfg["sort_frequency"])
        .add_agents(n, position=agents["position"], diameter=cfg["diameter"],
                    kind=agents["kind"], t_inf=jnp.zeros(n, jnp.float32),
                    tag=agents["tag"])
        .use(
            random_movement(cfg["max_movement"]),
            sir_infection(cfg["infection_radius"],
                          cfg["infection_probability"]),
            sir_recovery(cfg["recovery_probability"]),
        )
        .op(infectious_time_op, name="infectious_time", phase="post")
    )
