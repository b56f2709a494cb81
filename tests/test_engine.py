"""Engine integration tests: Algorithm 8 semantics + use-case physics."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    INFECTED,
    RECOVERED,
    SUSCEPTIBLE,
    EngineConfig,
    ForceParams,
    apoptosis,
    brownian_motion,
    cell_division,
    count_kinds,
    growth,
    init_state,
    make_pool,
    random_movement,
    run_jit,
    simulation_step,
    sir_infection,
    sir_recovery,
    spec_for_space,
)


def _sir_setup(n=300, n_inf=30, space=60.0, cap=None):
    cap = cap or n
    key = jax.random.PRNGKey(0)
    pos = jax.random.uniform(key, (n, 3), minval=0.0, maxval=space)
    kind = jnp.where(jnp.arange(n) < n_inf, INFECTED, SUSCEPTIBLE)
    pool = make_pool(cap, pos, diameter=1.0, kind=kind)
    spec = spec_for_space(0.0, space, 5.0, max_per_cell=64)
    config = EngineConfig(
        spec=spec,
        behaviors=(
            random_movement(2.0),
            sir_infection(infection_radius=4.0, infection_probability=0.25),
            sir_recovery(0.02),
        ),
        dt=1.0,
        min_bound=0.0,
        max_bound=space,
        boundary="toroidal",
    )
    return config, init_state(pool, seed=7)


def test_sir_population_conserved():
    config, state = _sir_setup()
    # n_kinds explicit: under scan the output shape must be static, and
    # RECOVERED is not present at t=0 so derivation could not see it anyway.
    final, counts = run_jit(config, state, 60,
                            collect=functools.partial(count_kinds, n_kinds=3))
    counts = np.asarray(counts)
    assert (counts.sum(axis=1) == 300).all()
    # epidemic dynamics: infections happened, recoveries happened
    assert counts[-1, 2] > 0
    assert counts[:, 0].min() < 270


def test_sir_monotone_recovered():
    config, state = _sir_setup()
    _, counts = run_jit(config, state, 40,
                        collect=functools.partial(count_kinds, n_kinds=3))
    rec = np.asarray(counts)[:, RECOVERED]
    assert (np.diff(rec) >= 0).all()


def test_toroidal_boundary_keeps_agents_inside():
    config, state = _sir_setup()
    final, _ = run_jit(config, state, 30)
    pos = np.asarray(final.pool.position)[np.asarray(final.pool.alive)]
    assert (pos >= 0.0).all() and (pos < 60.0).all()


def test_growth_division_population_doubles():
    pool = make_pool(64, jnp.full((8, 3), 20.0) + 3.0 * jnp.arange(8)[:, None], diameter=8.0)
    config = EngineConfig(
        spec=spec_for_space(0.0, 50.0, 10.0, max_per_cell=64),
        behaviors=(growth(200.0, 12.0), cell_division(1.0, trigger_diameter=11.99)),
        force_params=ForceParams(),
        dt=1.0,
        min_bound=0.0,
        max_bound=50.0,
        boundary="closed",
    )
    state = init_state(pool, seed=3)
    final, _ = run_jit(config, state, 8)
    # every cell divides once by ~step 4 and the daughters once more by ~step 8
    assert int(final.pool.num_alive()) in (16, 32)
    assert int(final.pool.overflow) == 0


def test_apoptosis_shrinks_population():
    pool = make_pool(128, jax.random.uniform(jax.random.PRNGKey(1), (100, 3), minval=0, maxval=40))
    config = EngineConfig(
        spec=spec_for_space(0.0, 40.0, 5.0, max_per_cell=64),
        behaviors=(apoptosis(0.2, min_age=0.0),),
        dt=1.0,
        min_bound=0.0,
        max_bound=40.0,
    )
    state = init_state(pool, seed=5)
    final, _ = run_jit(config, state, 10)
    assert int(final.pool.num_alive()) < 100


def test_step_is_deterministic():
    config, state = _sir_setup()
    a = simulation_step(config, state)
    b = simulation_step(config, state)
    np.testing.assert_array_equal(np.asarray(a.pool.kind), np.asarray(b.pool.kind))
    np.testing.assert_array_equal(np.asarray(a.pool.position), np.asarray(b.pool.position))


def test_force_relaxation_separates_overlap():
    """Two overlapping cells relax apart under Eq 4.1 (no behaviors)."""
    pool = make_pool(8, jnp.array([[10.0, 10, 10], [10.6, 10, 10]]), diameter=1.0)
    config = EngineConfig(
        spec=spec_for_space(0.0, 20.0, 2.0),
        force_params=ForceParams(),
        dt=0.2,
        min_bound=0.0,
        max_bound=20.0,
    )
    state = init_state(pool)
    final, _ = run_jit(config, state, 50)
    p = np.asarray(final.pool.position)
    gap = np.linalg.norm(p[0] - p[1])
    assert gap > 0.8  # pushed apart toward the ~equilibrium separation


# --------------------------------------------------- neighbor-dataflow audit

def _counting_candidates(monkeypatch):
    """Count candidate_neighbors_arrays invocations during one step trace."""
    import repro.core.neighbors as nb

    calls = {"n": 0}
    real = nb.candidate_neighbors_arrays

    def counted(*args, **kwargs):
        calls["n"] += 1
        return real(*args, **kwargs)

    monkeypatch.setattr(nb, "candidate_neighbors_arrays", counted)
    return calls


def test_step_builds_candidates_exactly_once(monkeypatch):
    """Regression: the seed built the dense (N, 27M) candidate tensor twice
    per step (simulation_step + mechanical_forces).  With candidate-hungry
    behaviors AND forces AND static detection in one step, it must now be
    built exactly once."""
    calls = _counting_candidates(monkeypatch)
    config, state = _sir_setup()
    config = dataclasses.replace(config, force_params=ForceParams())
    simulation_step(config, state)  # unjitted: counts python-level invocations
    assert calls["n"] == 1


def test_fused_step_builds_no_candidates(monkeypatch):
    """force_impl='fused' without candidate-reading behaviors or the overflow
    fallback never materializes the dense candidate tensor at all."""
    calls = _counting_candidates(monkeypatch)
    pool = make_pool(32, jnp.asarray(np.random.default_rng(0).uniform(0, 30, (20, 3)), jnp.float32), diameter=2.0)
    config = EngineConfig(
        spec=spec_for_space(0.0, 30.0, 5.0, max_per_cell=16),
        force_params=ForceParams(),
        dt=0.1,
        min_bound=0.0,
        max_bound=30.0,
        force_impl="fused",
        fused_overflow_fallback=False,
    )
    simulation_step(config, init_state(pool, seed=0))
    assert calls["n"] == 0


def test_fused_fallback_builds_candidates_once(monkeypatch):
    """With the overflow fallback enabled the dense tensor appears only in
    the lax.cond fallback branch — traced once, not duplicated."""
    calls = _counting_candidates(monkeypatch)
    pool = make_pool(32, jnp.asarray(np.random.default_rng(0).uniform(0, 30, (20, 3)), jnp.float32), diameter=2.0)
    config = EngineConfig(
        spec=spec_for_space(0.0, 30.0, 5.0, max_per_cell=16),
        force_params=ForceParams(),
        dt=0.1,
        min_bound=0.0,
        max_bound=30.0,
        force_impl="fused",
    )
    simulation_step(config, init_state(pool, seed=0))
    assert calls["n"] == 1


@pytest.mark.parametrize(
    "field,build",
    [
        ("force_impl", lambda spec: EngineConfig(spec=spec, force_impl="fusd")),
        ("diffusion_impl",
         lambda spec: EngineConfig(spec=spec, diffusion_impl="palas")),
        ("tile_order", lambda spec: EngineConfig(spec=spec, tile_order="z")),
        ("rank_impl",
         lambda spec: dataclasses.replace(spec, rank_impl="argsort")),
    ],
)
def test_unknown_impl_strings_rejected(field, build):
    """A typo'd impl must fail at construction, naming the valid choices —
    never quietly run the reference path."""
    spec = spec_for_space(0.0, 10.0, 2.0, max_per_cell=4)
    with pytest.raises(ValueError, match=f"unknown {field}.*expected one of"):
        build(spec)
