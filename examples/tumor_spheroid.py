"""Oncology use case (paper §4.6.2, Fig 4.16): tumor spheroid growth.

MCF-7-style mono-culture: cells grow (volume rate), divide above a trigger
probability, die stochastically past a minimum age, and random-walk
(Brownian) — Algorithm 2 with the Table 4.2 parameter structure.  The
observable is the spheroid diameter over time (from the bounding radius of
the population), which must grow monotonically and the population must
expand from its seed, mirroring the in-vitro curves.

Model-API demo (DESIGN.md §6): the model is one declarative `Simulation`
with capacity headroom for division (`capacity=4096` over 60 seed cells)
and a custom mask-gated `radial_census` post op (frequency 8 — §4.4.4
multi-scale); the chunked run drives the built triple's evolving state.

Run:  python examples/tumor_spheroid.py [--smoke]
"""

import argparse
import dataclasses
import time

import jax.numpy as jnp
import numpy as np

from repro import Simulation
from repro.core import (
    ForceParams,
    Operation,
    apoptosis,
    brownian_motion,
    cell_division,
    growth,
)
from repro.launch.compile_cache import enable_compile_cache


def radial_census_op(center: float, frequency: int = 8) -> Operation:
    """Custom standalone op: distance-from-seed census every ``frequency``
    steps.  Cheap elementwise work → "mask" gating (predicated select, no
    control flow) rather than the lax.cond gate the expensive ops use."""

    def fn(ctx, state):
        pool = state.pool
        r = jnp.linalg.norm(pool.position - center, axis=-1)
        return dataclasses.replace(
            state, pool=pool.set_attr("radial", jnp.where(pool.alive, r, 0.0))
        )

    return Operation("radial_census", fn, phase="post",
                     frequency=frequency, gate="mask")


def spheroid_diameter(pool) -> float:
    alive = np.asarray(pool.alive)
    pos = np.asarray(pool.position)[alive]
    if len(pos) < 2:
        return 0.0
    center = pos.mean(axis=0)
    r95 = np.quantile(np.linalg.norm(pos - center, axis=1), 0.95)
    return float(2.0 * r95)


def main(n_init=60, capacity=4096, steps=240, seed=0, smoke=False):
    if smoke:
        n_init, capacity, steps = 24, 512, 12
    space = 300.0
    rng = np.random.default_rng(seed)
    # seed cluster at the center
    pos = (150.0 + rng.normal(0, 12.0, (n_init, 3))).astype(np.float32)

    built = (
        Simulation(space=(0.0, space), cell_size=18.0, boundary="closed",
                   dt=1.0, capacity=capacity, max_per_cell=96, seed=seed)
        .add_agents(n_init, position=pos, diameter=14.0, radial=0.0)
        .use(
            brownian_motion(0.15),                 # Table 4.2 random movement
            growth(60.0, 18.0),                    # μm³/h to max diameter
            cell_division(0.02, trigger_diameter=17.0),
            apoptosis(0.002, min_age=87.0),        # min age to apoptosis [h]
        )
        .mechanics(ForceParams())
        .op(radial_census_op(150.0))
        .build()
    )
    state = built.state
    d0 = spheroid_diameter(state.pool)
    n0 = int(state.pool.num_alive())

    diam = []
    t0 = time.time()
    for chunk in range(6):
        state, _ = built.run_jit(steps // 6, state=state)
        diam.append(spheroid_diameter(state.pool))
    wall = time.time() - t0

    n1 = int(state.pool.num_alive())
    print(f"tumor spheroid: {n0} → {n1} cells over {steps} h "
          f"({wall:.1f}s wall), overflow={int(state.pool.overflow)}")
    print("diameter trajectory (μm):",
          " ".join(f"{d:.0f}" for d in [d0] + diam))
    radial = np.asarray(state.pool.get("radial"))[np.asarray(state.pool.alive)]
    print(f"radial census (custom op, freq 8): "
          f"p95 radius {np.quantile(radial, 0.95):.0f} μm")
    assert radial.max() > 0.0, "radial census op did not fire"
    if smoke:
        assert n1 >= n0, "population shrank in a growth-dominated smoke run"
        print("smoke run OK (facade model built + stepped, census fired)")
        return
    assert n1 > 1.5 * n0, "population did not grow"
    assert diam[-1] > d0 * 1.2, "spheroid did not expand"
    # growth is roughly monotone (small stochastic dips allowed)
    assert diam[-1] >= max(diam[:3]) * 0.9
    print("spheroid growth dynamics reproduced ✓ (cf. Fig 4.16)")


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny run for CI: build + step, skip the science bar")
    main(smoke=ap.parse_args().smoke)
