"""Neuroscience use case (paper §4.6.1, Fig 4.13): chemically-guided
neurite growth.

The paper grows pyramidal-cell dendrites as chains of segment agents whose
growth cones extend toward a chemical cue (Algorithm 1): direction =
w_old·previous + w_grad·gradient + w_rand·random, with branching.  This
example reproduces that model with the engine's primitives:

  * a static attractant gradient (GaussianBand-style, high at z = top);
  * *growth-cone* agents (kind=1) that move by the Algorithm-1 direction
    rule and deposit *trail* agents (kind=0) behind them — the trail is the
    neurite shaft, mechanically present but immediately static;
  * stochastic bifurcation: a growth cone divides with small probability
    (both daughters keep growing).

This is exactly the §5.5 performance regime the paper calls out: "activity
was limited to a neurite growth front, while the rest of the simulation
remained static" — so the run reports the static-agent fraction, and the
engine's work compaction keeps per-step cost proportional to the front
(the compacted branch now builds only the active set's candidate rows
through the lazy NeighborContext — see `mechanical_forces`).

Model-API demo (DESIGN.md §6): the model is one declarative `Simulation` —
a typed (3,)-vector `direction` attr plus scalar `path_len`, a static cue
declared as an initial-concentration substance with `diffusion_frequency=0`,
§5.5 work compaction via `mechanics(active_capacity=...)`, and a custom
`path_length` post op off the scheduler's `pre_positions` snapshot.

Run:  python examples/neurite_growth.py [--smoke]
"""

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import Simulation
from repro.core import ForceParams, add_agents
from repro.core.behaviors import StepContext
from repro.core.diffusion import gradient_at
from repro.launch.compile_cache import enable_compile_cache

TRAIL, CONE = 0, 1


def path_length_op(ctx, state):
    """Custom standalone op: arc length grown by each cone this step."""
    pool = state.pool
    seg = jnp.linalg.norm(pool.position - ctx.pre_positions, axis=-1)
    # Gate on the env-build alive snapshot: a cone spawned mid-step sits
    # in a slot whose pre_positions entry is the dead slot's stale value,
    # which would add one bogus |spawn_position| increment at birth.
    grew = pool.alive & ctx.neighbors.query_alive & (pool.kind == CONE)
    return dataclasses.replace(
        state,
        pool=pool.set_attr(
            "path_len", pool.get("path_len") + jnp.where(grew, seg, 0.0)
        ),
    )


def neurite_extension(grid_name: str, speed: float, w_old: float,
                      w_grad: float, w_rand: float, branch_prob: float,
                      target_z: float = 1e9):
    """Algorithm 1 as a behavior: move cones, deposit trail, bifurcate.
    Cones retire (→ TRAIL) on reaching the target band — growth terminates
    at the cue, letting the finished arbor go §5.5-static."""

    def run(ctx: StepContext, pool):
        ctx, key = ctx.next_rng()
        k_dir, k_branch = jax.random.split(key)
        # retire cones that reached the target band
        reached = pool.alive & (pool.kind == CONE) & (pool.position[:, 2] >= target_z)
        pool = pool.replace(kind=jnp.where(reached, TRAIL, pool.kind))
        cones = pool.alive & (pool.kind == CONE)

        grad = gradient_at(ctx.grids[grid_name], pool.position, normalized=True)
        prev = pool.get("direction")
        rand = jax.random.normal(k_dir, pool.position.shape)
        rand = rand / jnp.maximum(jnp.linalg.norm(rand, axis=-1, keepdims=True), 1e-12)
        direction = w_old * prev + w_grad * grad + w_rand * rand
        direction = direction / jnp.maximum(
            jnp.linalg.norm(direction, axis=-1, keepdims=True), 1e-12
        )

        # deposit a trail segment at the cone's current position (slightly
        # thinner than the extension step so consecutive segments just touch
        # — the settled shaft then produces zero net force and goes §5.5-static)
        pool = add_agents(
            pool,
            spawn_mask=cones,
            position=pool.position,
            diameter=pool.diameter * 0.8,
            kind=jnp.full((pool.capacity,), TRAIL, jnp.int32),
        )
        # … and advance the cone
        new_pos = pool.position + direction * speed
        pool = pool.replace(
            position=jnp.where(cones[:, None], new_pos, pool.position)
        )
        pool = pool.set_attr(
            "direction", jnp.where(cones[:, None], direction, prev)
        )

        # bifurcation: a cone spawns a second cone at a slight offset
        u = jax.random.uniform(k_branch, (pool.capacity,))
        branch = cones & (u < branch_prob)
        side = jnp.cross(direction, jnp.array([1.0, 0.0, 0.0]))
        side = side / jnp.maximum(jnp.linalg.norm(side, axis=-1, keepdims=True), 1e-12)
        pool = add_agents(
            pool,
            spawn_mask=branch,
            position=pool.position + side * 1.2 * pool.diameter[:, None],
            diameter=pool.diameter,
            kind=jnp.full((pool.capacity,), CONE, jnp.int32),
            attrs={"direction": side},
        )
        return ctx, pool

    return run


def main(n_neurons=16, steps=120, space=120.0, seed=0, smoke=False):
    if smoke:
        n_neurons, steps = 4, 12
    rng = np.random.default_rng(seed)
    # somata on the bottom plate, apical cones pointing up
    xy = rng.uniform(20, space - 20, (n_neurons, 2))
    pos = np.concatenate([xy, np.full((n_neurons, 1), 10.0)], axis=1).astype(np.float32)

    # attractant: static gradient increasing with z (GaussianBand at the top)
    res = 24
    zs = (np.arange(res) + 0.5) * (space / res)
    conc = np.exp(-((zs - space) ** 2) / (2 * 40.0**2))
    cue = np.broadcast_to(conc[None, None, :], (res, res, res)).astype(np.float32)

    built = (
        Simulation(space=(0.0, space), cell_size=4.0, boundary="closed",
                   dt=0.5, capacity=8192, max_per_cell=128, seed=seed,
                   diffusion_frequency=0)        # static cue (paper: "static substances")
        .add_agents(
            n_neurons, position=pos, diameter=2.0,
            kind=np.full((n_neurons,), CONE, np.int32),
            direction=np.tile(np.array([[0.0, 0.0, 1.0]], np.float32),
                              (n_neurons, 1)),
            path_len=0.0,
        )
        .add_substance("guide", diffusion=0.0, resolution=res, concentration=cue)
        .use(neurite_extension("guide", speed=2.4, w_old=4.0, w_grad=1.5,
                               w_rand=0.6, branch_prob=0.02, target_z=104.0))
        # §5.5: cost follows the growth front (subset candidate rows only)
        .mechanics(ForceParams(static_tolerance=1e-3), active_capacity=2048)
        .op(path_length_op, name="path_length", phase="post")
        .build()
    )
    state = built.state
    t0 = time.time()
    for _ in range(4):
        state, _ = built.run_jit(steps // 4, state=state)
    wall = time.time() - t0

    alive = int(state.pool.num_alive())
    kinds = np.asarray(state.pool.kind)[np.asarray(state.pool.alive)]
    n_cones = int((kinds == CONE).sum())
    n_trail = int((kinds == TRAIL).sum())
    static_frac = float(jnp.sum(state.pool.static) / jnp.maximum(state.pool.num_alive(), 1))
    z = np.asarray(state.pool.position)[np.asarray(state.pool.alive)][:, 2]

    print(f"neurite growth: {n_neurons} neurons → {alive} agents "
          f"({n_cones} active cones, {n_trail} trail/retired) in {wall:.1f}s")
    print(f"static fraction {static_frac:.2f}; apical reach z = {z.max():.1f} "
          f"(soma at 10.0, cue at {space:.0f})")
    path = np.asarray(state.pool.get("path_len"))[np.asarray(state.pool.alive)]
    print(f"arc length (custom op): max {path.max():.0f} μm "
          f"(straight-line soma→cue ≈ {104.0 - 10.0:.0f} μm)")
    if smoke:
        assert alive > n_neurons, "no trail deposited in smoke run"
        assert path.max() > 0.0, "path-length op did not fire"
        print("smoke run OK (facade model built + stepped, trail deposited)")
        return alive, static_frac
    assert path.max() > 60.0, "path-length op did not accumulate along growth"
    # each lineage deposits ≈ (target_z − soma_z)/speed ≈ 39 segments
    assert n_trail > n_neurons * 30, "trail not deposited"
    # bifurcations multiply lineages: total agents well beyond single shafts
    assert alive > n_neurons * 45, "no bifurcations happened"
    assert z.max() > 60.0, "growth did not follow the chemical cue"
    assert static_frac > 0.6, "arbor did not become static (§5.5 regime)"
    print("chemically-guided arborization reproduced ✓ (cf. Fig 4.13)")
    return alive, static_frac


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny run for CI: build + step, skip the science bar")
    main(smoke=ap.parse_args().smoke)
