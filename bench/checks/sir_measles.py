"""Correctness of a measles-SIR chunk against the plain reference.

The reference steps the program's state from the start of the compared
chunk through the same iterations, as the model defines them (Algorithms
3-4, sec 4.6.3): the layout sort, then per agent random movement, infection
when an agent that was infected at the iteration's start stood within the
infection radius of the agent's new position (every agent reads the others
as they were when the iteration began), and recovery; then the boundary,
ageing and the infectious-time op.  Draws follow the run's key as the model
defines it (the step's key folded from the run's key, one split per
stochastic behaviour).  In a toroidal space the distance is to the nearest
image, so agents infect across the faces.  Agents are matched by tag.

``witness`` is the same reference with the program's own search: only the
agents of the 27 boxes around the query's box at the iteration's start, no
wrap across faces.  It is no check: it shows where the program departs from
the model (PERF.md, Open questions).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import reference as ref
from checks import common

SUSCEPTIBLE, INFECTED, RECOVERED = 0, 1, 2

# The states, the layout and the infectious times are compared exactly and
# positions to 1e-2.  The program meets these against its own search (the
# witness) on every seed read, and fails them against the model on every
# seed: the configuration is held out of BENCHMARK.json until the program's
# infection search follows the model (PERF.md, Open questions).
LIMITS = {
    "layout_mismatch": 0,
    "kind_mismatch": 0,
    "position_gap": 1e-2,
    "t_inf_gap": 0,
}


def step(cfg: dict, s: dict, dtype, search: str = "model") -> dict:
    lo, hi = cfg["space"]
    n, box = ref.grid_dims(lo, hi, cfg["cell_size"])
    dt = cfg["dt"]
    torus = cfg["boundary"] == "toroidal"
    s = common.sort_if_due(s, cfg, lo, box, n)
    pos = s["position"].astype(dtype)
    alive, kind = s["alive"], s["kind"]
    start_pos, start_kind = pos, kind
    key = ref.step_key(s["rng"], s["step"])

    key, use = ref.next_key(key)
    vec = jax.random.uniform(use, pos.shape, jnp.float32, -1.0, 1.0)
    norm = jnp.sqrt(jnp.sum(vec * vec, axis=-1, keepdims=True))
    move = vec / jnp.maximum(norm, 1e-12) * cfg["max_movement"]
    pos = pos + jnp.where(alive[:, None], move, 0).astype(dtype)

    key, use = ref.next_key(key)
    u = jax.random.uniform(use, (pos.shape[0],))
    if search == "model":
        exposed = ref.any_close(pos, start_pos, start_kind == INFECTED, alive,
                                lo, hi, n, cfg["infection_radius"], dtype,
                                torus)
    else:
        exposed = ref.any_close(pos, start_pos, start_kind == INFECTED, alive,
                                lo, hi, n, cfg["infection_radius"], dtype,
                                False, ref.cell_coords(start_pos, lo, box, n))
    infect = (alive & (kind == SUSCEPTIBLE) & exposed
              & (u < cfg["infection_probability"]))
    kind = jnp.where(infect, INFECTED, kind)

    key, use = ref.next_key(key)
    u = jax.random.uniform(use, (pos.shape[0],))
    recover = alive & (kind == INFECTED) & (u < cfg["recovery_probability"])
    kind = jnp.where(recover, RECOVERED, kind)

    if torus:
        pos = lo + jnp.mod(pos - lo, hi - lo)
    else:
        pos = jnp.clip(pos, lo, hi)
    attrs = dict(s["attrs"])
    attrs["t_inf"] = attrs["t_inf"] + jnp.where(
        alive & (kind == INFECTED), dt, 0)
    return dict(s, position=pos.astype(dtype), kind=kind, attrs=attrs,
                age=s["age"] + jnp.where(alive, dt, 0), step=s["step"] + 1)


def reference(cfg: dict, s: dict, n_steps: int, dtype=jnp.float32,
              search: str = "model") -> dict:
    s = common.on_device(s)
    for _ in range(n_steps):
        s = step(cfg, s, dtype, search)
    return common.to_host(s)


witness = functools.partial(reference, search="program")


def compare(cfg: dict, got: dict, want: dict) -> dict:
    g, w = common.by_tag(got), common.by_tag(want)
    d = np.abs(g["position"] - w["position"])
    if cfg["boundary"] == "toroidal":
        edge = cfg["space"][1] - cfg["space"][0]
        d = np.minimum(d, edge - d)      # distance per axis on the torus
    return {
        "layout_mismatch": common.layout_mismatch(got, want),
        "kind_mismatch": int((g["kind"] != w["kind"]).sum()),
        "position_gap": float(d.max()),
        "t_inf_gap": float(np.abs(g["attrs"]["t_inf"]
                                  - w["attrs"]["t_inf"]).max()),
    }
