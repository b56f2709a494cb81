"""Fused cell-list force path: kernel parity + engine dataflow regressions.

No hypothesis dependency — these must run everywhere."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    EngineConfig,
    ForceParams,
    build_index,
    init_state,
    make_pool,
    mechanical_forces,
    run_jit,
    simulation_step,
    spec_for_space,
)
from repro.core.forces import update_static_flags, update_static_flags_celllist
from repro.core.grid import build_index_arrays, candidate_neighbors
from repro.kernels.cell_force import kernel as cf_kernel
from repro.kernels.cell_force import ops as cf_ops


def _random_pool(rng, n, cap, space, diameter=(1.0, 6.0), dead_frac=0.2):
    pos = rng.uniform(0, space, (n, 3)).astype(np.float32)
    diam = rng.uniform(*diameter, (n,)).astype(np.float32)
    pool = make_pool(cap, jnp.asarray(pos), diameter=jnp.asarray(diam))
    if dead_frac > 0:
        kill_ids = rng.choice(n, max(int(n * dead_frac), 1), replace=False)
        kill = jnp.zeros((cap,), bool).at[jnp.asarray(kill_ids)].set(True)
        pool = pool.replace(alive=pool.alive & ~kill)
    return pool


# ------------------------------------------------------------ kernel parity

@pytest.mark.parametrize(
    "n,cap,space,radius,m",
    [
        (60, 80, 30.0, 3.0, 16),     # generic
        (200, 256, 40.0, 5.0, 32),   # denser, bigger cells
        (30, 64, 12.0, 6.0, 32),     # tiny grid (2x2x2): every cell on boundary
        (5, 8, 10.0, 5.0, 4),        # near-empty
    ],
)
def test_kernel_matches_oracle(n, cap, space, radius, m):
    rng = np.random.default_rng(n + m)
    pool = _random_pool(rng, n, cap, space)
    spec = spec_for_space(0.0, space, radius, max_per_cell=m)
    index = build_index(spec, pool)
    assert not bool(index.overflowed)
    args = (pool.position, pool.radius(), index.cell_list,
            index.slot_of_agent, spec.dims)
    ref = cf_ops.cell_list_force(*args, impl="reference")
    pal = cf_ops.cell_list_force(*args, impl="pallas")
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref), atol=1e-5)


def _scatter_add_oracle(position, radius, cell_list, dims, num_out):
    """The slot forces' return to agent order as a scatter-add over every
    slot of the cell list, out-of-range (empty or ghost) slots dropped."""
    n_cells, m = cell_list.shape
    cpos, crad, cval = cf_ops._cell_major_planar(
        position, radius, cell_list, dims
    )
    slot_force = cf_kernel.cell_list_force_planar(cpos, crad, cval, dims)
    return jnp.zeros((num_out, 3), jnp.float32).at[cell_list.reshape(-1)].add(
        slot_force.reshape(3, n_cells * m).T, mode="drop"
    )


def _clustered_pool(rng):
    """Ten agents packed into one corner cell (it overflows at M = 4),
    forty spread out."""
    pos = np.concatenate(
        [rng.uniform(1.0, 2.0, (10, 3)), rng.uniform(0, 30.0, (40, 3))]
    ).astype(np.float32)
    return make_pool(64, jnp.asarray(pos), diameter=3.0)


@pytest.mark.parametrize("case", ["generic", "dead", "overflow", "ghost"])
def test_slot_gather_matches_scatter_add(case):
    """Gathering each agent's force through ``slot_of_agent`` is bit for
    bit the scatter-add of every slot (each agent holds at most one slot):
    dead and dropped agents read 0, and ghost rows past ``num_out`` never
    reach the output."""
    rng = np.random.default_rng(23)
    num_out = None
    if case == "overflow":
        pool = _clustered_pool(rng)
        spec = spec_for_space(0.0, 30.0, 3.0, max_per_cell=4)
    else:
        pool = _random_pool(
            rng, 120, 160, 40.0, dead_frac=0.5 if case == "dead" else 0.0
        )
        spec = spec_for_space(0.0, 40.0, 5.0, max_per_cell=16)
    position, radius, alive = pool.position, pool.radius(), pool.alive
    if case == "ghost":
        # Local rows first, then ghosts, as in the distributed engine's
        # ghost-extended build; some ghost rows dead.
        ghosts = _random_pool(rng, 40, 48, 40.0, dead_frac=0.25)
        num_out = pool.capacity
        position = jnp.concatenate([position, ghosts.position])
        radius = jnp.concatenate([radius, ghosts.radius()])
        alive = jnp.concatenate([alive, ghosts.alive])
    index = build_index_arrays(spec, position, alive)
    out_n = position.shape[0] if num_out is None else num_out
    args = (position, radius, index.cell_list, index.slot_of_agent, spec.dims)

    got = cf_ops.cell_list_force(*args, impl="pallas", num_out=num_out)
    want = _scatter_add_oracle(
        position, radius, index.cell_list, spec.dims, out_n
    )
    assert got.shape == (out_n, 3)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    ref = cf_ops.cell_list_force(*args, impl="reference", num_out=num_out)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)

    n_cells, m = index.cell_list.shape
    unlisted = np.asarray(index.slot_of_agent[:out_n]) == n_cells * m
    assert np.all(np.asarray(got)[unlisted] == 0.0)
    if case == "overflow":
        assert bool(index.overflowed)
        assert np.any(unlisted & np.asarray(alive))
    else:
        assert not bool(index.overflowed)
    if case in ("dead", "ghost"):
        assert np.any(~np.asarray(alive[:out_n]))


def test_fused_matches_reference_forces():
    """force_impl='fused' vs the dense candidate path, incl. dead agents and
    agents in boundary cells (agents sit right at the domain faces)."""
    rng = np.random.default_rng(7)
    pool = _random_pool(rng, 150, 200, 40.0)
    # pin some agents onto the boundary faces
    pinned = pool.position.at[:10, 0].set(0.0).at[10:20, 1].set(39.999)
    pool = pool.replace(position=pinned)
    spec = spec_for_space(0.0, 40.0, 5.0, max_per_cell=32)
    index = build_index(spec, pool)
    ref = mechanical_forces(spec, index, pool, ForceParams(), impl="reference")
    fused = mechanical_forces(spec, index, pool, ForceParams(), impl="fused")
    assert float(jnp.max(jnp.abs(fused - ref))) < 1e-5


def test_fused_overflow_falls_back_to_reference():
    """An overflowing cell would truncate pair forces; the lax.cond fallback
    must reproduce the dense path exactly."""
    pool = _clustered_pool(np.random.default_rng(1))
    spec = spec_for_space(0.0, 30.0, 3.0, max_per_cell=4)
    index = build_index(spec, pool)
    assert bool(index.overflowed)
    ref = mechanical_forces(spec, index, pool, ForceParams(), impl="reference")
    fused = mechanical_forces(
        spec, index, pool, ForceParams(), impl="fused", fused_fallback=True
    )
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref), atol=1e-6)


def test_fused_custom_params():
    rng = np.random.default_rng(5)
    pool = _random_pool(rng, 80, 96, 25.0, dead_frac=0.0)
    spec = spec_for_space(0.0, 25.0, 5.0, max_per_cell=32)
    index = build_index(spec, pool)
    params = ForceParams(repulsion_k=5.0, attraction_gamma=0.3)
    ref = mechanical_forces(spec, index, pool, params, impl="reference")
    fused = mechanical_forces(spec, index, pool, params, impl="fused")
    assert float(jnp.max(jnp.abs(fused - ref))) < 1e-5


# --------------------------------------------- Morton window kernel (ISSUE 8)

@pytest.mark.parametrize(
    "n,cap,space,radius,m,block",
    [
        (60, 80, 30.0, 3.0, 16, 32),
        (200, 256, 40.0, 5.0, 32, 64),
        (30, 64, 12.0, 6.0, 32, 16),   # tiny grid: every cell on boundary
        (5, 8, 10.0, 5.0, 4, 8),       # near-empty
    ],
)
def test_window_kernel_matches_oracle_full_window(n, cap, space, radius, m, block):
    """With window ≥ #blocks the sweep is masked all-pairs, exact for ANY
    layout — tests the kernel's pair math/masking without needing a sorted
    pool."""
    rng = np.random.default_rng(n + m)
    pool = _random_pool(rng, n, cap, space)
    spec = spec_for_space(0.0, space, radius, max_per_cell=m)
    index = build_index(spec, pool)
    assert not bool(index.overflowed)
    ref = cf_ops.cell_list_force(
        pool.position, pool.radius(), index.cell_list, index.slot_of_agent,
        spec.dims, impl="reference",
    )
    win = cf_ops.cell_window_force(
        pool.position, pool.radius(), index.cell_of_agent, spec.dims,
        block=block, window=-(-cap // block),
    )
    np.testing.assert_allclose(np.asarray(win), np.asarray(ref), atol=1e-5)


def test_window_kernel_sorted_narrow_window():
    """On a layout-sorted pool a narrow window must already cover every
    neighborhood (certified by _morton_window_ok) and match the oracle."""
    from repro.core import sort_agents
    from repro.core.forces import _morton_window_ok

    rng = np.random.default_rng(3)
    pool = _random_pool(rng, 200, 256, 40.0)
    spec = spec_for_space(0.0, 40.0, 5.0, max_per_cell=32)
    pool = sort_agents(spec, pool)
    index = build_index(spec, pool, assume_sorted=True)
    assert bool(_morton_window_ok(spec, index, 32, 3))
    ref = cf_ops.cell_list_force(
        pool.position, pool.radius(), index.cell_list, index.slot_of_agent,
        spec.dims, impl="reference",
    )
    win = cf_ops.cell_window_force(
        pool.position, pool.radius(), index.cell_of_agent, spec.dims,
        block=32, window=3,
    )
    np.testing.assert_allclose(np.asarray(win), np.asarray(ref), atol=1e-5)


def test_morton_dispatch_falls_back_when_window_violated():
    """An unsorted pool fails the coverage check, so tile_order='morton'
    with a narrow window must route through the linear fused path bit-
    exactly."""
    from repro.core.forces import _morton_window_ok

    rng = np.random.default_rng(9)
    pool = _random_pool(rng, 150, 192, 40.0)   # storage order = random order
    spec = spec_for_space(0.0, 40.0, 5.0, max_per_cell=32)
    index = build_index(spec, pool)
    assert not bool(_morton_window_ok(spec, index, 32, 1))
    linear = mechanical_forces(spec, index, pool, ForceParams(), impl="fused")
    morton = mechanical_forces(
        spec, index, pool, ForceParams(), impl="fused",
        tile_order="morton", morton_block=32, morton_window=1,
    )
    np.testing.assert_array_equal(np.asarray(morton), np.asarray(linear))


def test_engine_trajectories_match_morton():
    """Full engine at sort_frequency=1 with tile_order='morton' vs the
    linear fused engine — same trajectories to float tolerance."""
    rng = np.random.default_rng(17)
    pool = _random_pool(rng, 120, 160, 40.0)
    spec = spec_for_space(0.0, 40.0, 5.0, max_per_cell=32)
    state = init_state(pool, seed=2)
    lin, _ = run_jit(
        _engine_config(spec, 40.0, "fused", sort_frequency=1), state, 8
    )
    mor, _ = run_jit(
        _engine_config(
            spec, 40.0, "fused", sort_frequency=1,
            tile_order="morton", morton_block=32, morton_window=4,
        ),
        state, 8,
    )
    np.testing.assert_allclose(
        np.asarray(mor.pool.position), np.asarray(lin.pool.position), atol=1e-4
    )
    assert bool(jnp.all(mor.pool.alive == lin.pool.alive))


# ------------------------------------------------------- engine-level parity

def _engine_config(spec, space, impl, **kw):
    return EngineConfig(
        spec=spec,
        force_params=ForceParams(),
        dt=0.1,
        min_bound=0.0,
        max_bound=space,
        boundary="closed",
        force_impl=impl,
        **kw,
    )


def test_engine_trajectories_match():
    rng = np.random.default_rng(11)
    pool = _random_pool(rng, 120, 160, 40.0)
    spec = spec_for_space(0.0, 40.0, 5.0, max_per_cell=32)
    state = init_state(pool, seed=2)
    ref, _ = run_jit(_engine_config(spec, 40.0, "reference"), state, 8)
    fused, _ = run_jit(_engine_config(spec, 40.0, "fused"), state, 8)
    np.testing.assert_allclose(
        np.asarray(fused.pool.position), np.asarray(ref.pool.position), atol=1e-4
    )
    assert bool(jnp.all(ref.pool.static == fused.pool.static))


def test_celllist_static_flags_match_candidate_flags():
    rng = np.random.default_rng(13)
    pool = _random_pool(rng, 100, 128, 30.0)
    spec = spec_for_space(0.0, 30.0, 5.0, max_per_cell=32)
    index = build_index(spec, pool)
    disp = jnp.asarray(rng.normal(0, 1e-3, (128, 3)), jnp.float32)
    cand, mask = candidate_neighbors(spec, index, pool)
    ref = update_static_flags(pool, disp, cand, mask, ForceParams())
    cl = update_static_flags_celllist(spec, index, pool, disp, ForceParams())
    np.testing.assert_array_equal(np.asarray(ref.static), np.asarray(cl.static))
