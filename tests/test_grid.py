"""Uniform-grid neighbor search tests (§5.3.1, §5.4.2).

Includes the sort-free build parity suite: `build_index_arrays` (tiled-
histogram ranking, both impls) must be bit-exact vs the seed's argsort
build, kept as the test-only oracle in tests/grid_oracle.py.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from grid_oracle import build_index_arrays_argsort, sort_agents_argsort

from repro.core import (
    build_index,
    candidate_neighbors,
    make_pool,
    sort_agents,
    spec_for_space,
)
from repro.core.grid import GridSpec, build_index_arrays
from repro.core import morton
from repro.kernels.cell_rank import ops as cr_ops


def test_morton_roundtrip():
    xs = jnp.arange(0, 1024, 37, dtype=jnp.uint32)
    ys = (xs * 7) % 1024
    zs = (xs * 13) % 1024
    codes = morton.encode3(xs, ys, zs)
    rx, ry, rz = morton.decode3(codes)
    np.testing.assert_array_equal(np.asarray(rx), np.asarray(xs))
    np.testing.assert_array_equal(np.asarray(ry), np.asarray(ys))
    np.testing.assert_array_equal(np.asarray(rz), np.asarray(zs))


def test_morton_locality():
    """Agents in the same cell share a code; adjacent cells differ little in
    expectation — test the weaker exact property: same cell ⇒ same code."""
    a = morton.encode3(jnp.uint32(5), jnp.uint32(6), jnp.uint32(7))
    b = morton.encode3(jnp.uint32(5), jnp.uint32(6), jnp.uint32(7))
    assert int(a) == int(b)
    c = morton.encode3(jnp.uint32(5), jnp.uint32(6), jnp.uint32(8))
    assert int(a) != int(c)


# ----------------------------------------------- morton property tests (ISSUE 8)
# The sort-free permutation's bit-exactness proof leans on three facts about
# encode3: it is injective over the grid (so the Z-rank table is a
# permutation), strictly monotone per coordinate, and wraps mod
# max_grid_dim() rather than bleeding into other coordinates' bit lanes.


def _grid_codes(dims):
    nx, ny, nz = dims
    ix, iy, iz = np.meshgrid(
        np.arange(nx, dtype=np.uint32),
        np.arange(ny, dtype=np.uint32),
        np.arange(nz, dtype=np.uint32),
        indexing="ij",
    )
    return morton.encode3_np(ix, iy, iz).reshape(-1)


@settings(deadline=None, max_examples=30)
@given(
    nx=st.integers(1, morton.max_grid_dim()),
    ny=st.integers(1, morton.max_grid_dim()),
    nz=st.integers(1, morton.max_grid_dim()),
)
def test_morton_encode3_bijective_noncubic(nx, ny, nz):
    """encode3 is injective over any (possibly extremely non-cubic) grid with
    per-dimension sizes up to max_grid_dim() — the property that makes the
    trace-time Z-rank table a permutation and the counting-sort layout
    permutation bit-exact vs the argsort oracle."""
    # Keep the enumerated grid small while still exercising dims at the cap:
    # shrink the two largest dims until the product is enumerable.
    dims = [nx, ny, nz]
    while int(np.prod(dims)) > 1 << 16:
        dims[int(np.argmax(dims))] = (max(dims) + 1) // 2
    codes = _grid_codes(tuple(dims))
    assert np.unique(codes).size == codes.size


def test_morton_encode3_bijective_at_dim_cap():
    """Deterministic pins of the hypothesis search: grids with one or two
    dimensions AT max_grid_dim() stay collision-free."""
    for dims in [(1024, 8, 8), (4, 1024, 16), (3, 5, 1024), (1024, 64, 1),
                 (1, 1024, 64)]:
        codes = _grid_codes(dims)
        assert np.unique(codes).size == codes.size, dims


@settings(deadline=None, max_examples=50)
@given(
    x=st.integers(0, morton.max_grid_dim() - 2),
    y=st.integers(0, morton.max_grid_dim() - 1),
    z=st.integers(0, morton.max_grid_dim() - 1),
)
def test_morton_monotone_per_coordinate(x, y, z):
    """encode3 strictly increases when any single coordinate increments —
    with injectivity, this is why Z-rank order refines spatial order and the
    stable counting sort reproduces the argsort permutation exactly."""
    c = int(morton.encode3_np(np.uint32(x), np.uint32(y), np.uint32(z)))
    assert int(morton.encode3_np(np.uint32(x + 1), np.uint32(y), np.uint32(z))) > c
    if y + 1 < morton.max_grid_dim():
        assert int(morton.encode3_np(np.uint32(x), np.uint32(y + 1), np.uint32(z))) > c
    if z + 1 < morton.max_grid_dim():
        assert int(morton.encode3_np(np.uint32(x), np.uint32(y), np.uint32(z + 1))) > c


@settings(deadline=None, max_examples=20)
@given(octet=st.integers(0, (1 << 27) // 8 - 1), level=st.integers(1, 3))
def test_morton_zorder_locality(octet, level):
    """Z-order locality, both exact forms the morton force tiles rely on:
    (1) consecutive codes inside an aligned octet move by at most one step
    per coordinate; (2) an aligned run of 8**level codes decodes to an
    aligned 2**level cube — a contiguous block of layout ranks covers a
    compact 3D region."""
    run = 8 ** level
    base = (octet * 8 // run) * run
    codes = np.arange(base, base + run, dtype=np.uint32)
    xs, ys, zs = (np.asarray(v) for v in morton.decode3(jnp.asarray(codes)))
    # (1) within each octet, consecutive codes are Chebyshev-adjacent
    for lo in range(0, run, 8):
        dx = np.abs(np.diff(xs[lo:lo + 8].astype(np.int64)))
        dy = np.abs(np.diff(ys[lo:lo + 8].astype(np.int64)))
        dz = np.abs(np.diff(zs[lo:lo + 8].astype(np.int64)))
        assert dx.max(initial=0) <= 1 and dy.max(initial=0) <= 1 and dz.max(initial=0) <= 1
    # (2) the whole run is an aligned 2**level cube
    side = 1 << level
    for vs in (xs, ys, zs):
        assert vs.max() - vs.min() <= side - 1
        assert vs.min() % side == 0


def test_morton_out_of_range_wraps_not_bleeds():
    """Out-of-range regression: coordinates ≥ max_grid_dim() wrap mod 1024
    inside their own bit lane instead of corrupting the other coordinates,
    and the grid layer clips cell coords before ever encoding."""
    m = morton.max_grid_dim()
    a = morton.encode3(jnp.uint32(m), jnp.uint32(1), jnp.uint32(2))
    b = morton.encode3(jnp.uint32(0), jnp.uint32(1), jnp.uint32(2))
    assert int(a) == int(b)
    # max in-range code fills exactly 30 bits — no overflow into uint32 sign
    top = morton.encode3(jnp.uint32(m - 1), jnp.uint32(m - 1), jnp.uint32(m - 1))
    assert int(top) == (1 << 30) - 1
    # grid layer: positions far outside the domain land in clipped edge cells
    from repro.core.grid import cell_coords
    spec = spec_for_space(0.0, 20.0, 4.0, max_per_cell=8)
    wild = jnp.asarray([[-1e6, 5.0, 5.0], [5.0, 1e6, 5.0], [1e9, -1e9, 1e9]],
                       jnp.float32)
    ijk = np.asarray(cell_coords(spec, wild))
    assert ijk.min() >= 0
    assert (ijk < np.asarray(spec.dims)).all()


@settings(deadline=None, max_examples=20)
@given(
    nx=st.integers(1, 32), ny=st.integers(1, 32), nz=st.integers(1, 32),
    use_morton=st.booleans(),
)
def test_zorder_cells_is_permutation_inverse_of_cell_zrank(nx, ny, nz, use_morton):
    """The trace-time layout tables are mutually inverse permutations, and in
    morton mode they order cells by ascending Morton code."""
    dims = (nx, ny, nz)
    order = morton.zorder_cells(dims, use_morton)
    rank = morton.cell_zrank(dims, use_morton)
    n = nx * ny * nz
    assert sorted(order.tolist()) == list(range(n))
    np.testing.assert_array_equal(rank[order], np.arange(n, dtype=np.int32))
    np.testing.assert_array_equal(order[rank], np.arange(n, dtype=np.int32))
    if use_morton:
        codes = _grid_codes(dims)
        assert (np.diff(codes[order].astype(np.int64)) > 0).all()
    else:
        np.testing.assert_array_equal(order, np.arange(n, dtype=np.int32))


def _brute_force_neighbors(pos, radius):
    d2 = ((pos[:, None, :] - pos[None, :, :]) ** 2).sum(-1)
    n = pos.shape[0]
    within = (d2 <= radius**2) & ~np.eye(n, dtype=bool)
    return within


@settings(deadline=None, max_examples=20)
@given(
    n=st.integers(2, 120),
    seed=st.integers(0, 2**31 - 1),
    use_morton=st.booleans(),
)
def test_neighbor_completeness_property(n, seed, use_morton):
    """Every true neighbor within the interaction radius must appear in the
    candidate set (the grid may over-approximate, never under)."""
    rng = np.random.default_rng(seed)
    radius = 4.0
    pos = rng.uniform(0, 40, (n, 3)).astype(np.float32)
    pool = make_pool(n + 8, jnp.asarray(pos), diameter=1.0)
    spec = spec_for_space(0.0, 40.0, radius, max_per_cell=n + 8, use_morton=use_morton)
    index = build_index(spec, pool)
    assert not bool(index.overflowed)
    cand, mask = candidate_neighbors(spec, index, pool)
    cand, mask = np.asarray(cand), np.asarray(mask)
    within = _brute_force_neighbors(pos, radius)
    for i in range(n):
        found = set(cand[i][mask[i]].tolist())
        required = set(np.nonzero(within[i])[0].tolist())
        assert required.issubset(found), f"agent {i} missing {required - found}"


def test_overflow_detection():
    pos = jnp.zeros((10, 3)) + 5.0  # all agents in one cell
    pool = make_pool(16, pos)
    spec = GridSpec(origin=(0, 0, 0), box_size=10.0, dims=(4, 4, 4), max_per_cell=4)
    index = build_index(spec, pool)
    assert bool(index.overflowed)
    assert int(index.cell_count.max()) == 10


def test_sort_agents_groups_cells():
    rng = np.random.default_rng(1)
    pos = rng.uniform(0, 64, (200, 3)).astype(np.float32)
    pool = make_pool(256, jnp.asarray(pos))
    spec = spec_for_space(0.0, 64.0, 8.0)
    sorted_pool = sort_agents(spec, pool)
    # dead agents at the back
    alive = np.asarray(sorted_pool.alive)
    assert alive[:200].all() and not alive[200:].any()
    # agents in the same cell are contiguous after sorting
    from repro.core.grid import cell_coords, sort_key

    keys = np.asarray(sort_key(spec, cell_coords(spec, sorted_pool.position)))[:200]
    assert (np.diff(keys.astype(np.int64)) >= 0).all()


def test_cell_counts_match_population():
    rng = np.random.default_rng(2)
    pos = rng.uniform(0, 32, (100, 3)).astype(np.float32)
    pool = make_pool(128, jnp.asarray(pos))
    spec = spec_for_space(0.0, 32.0, 4.0)
    index = build_index(spec, pool)
    assert int(index.cell_count.sum()) == 100


# ---------------------------------------------------------------------------
# Sort-free build: bit-exact parity vs the argsort oracle (ISSUE 5).
# Both rank impls run with a coarse tile so the interpret-mode Pallas grid
# stays a handful of programs (see MEMORY: interpret cost ∝ grid programs).
# ---------------------------------------------------------------------------

def _assert_build_parity(spec, position, alive, tile=16):
    want = build_index_arrays_argsort(spec, position, alive)
    for impl in ("xla", "pallas"):
        got = build_index_arrays(
            dataclasses.replace(spec, rank_impl=impl),
            position, alive, rank_tile=tile,
        )
        np.testing.assert_array_equal(
            np.asarray(got.cell_of_agent), np.asarray(want.cell_of_agent),
            err_msg=f"cell_of_agent diverged ({impl})",
        )
        np.testing.assert_array_equal(
            np.asarray(got.cell_list), np.asarray(want.cell_list),
            err_msg=f"cell_list diverged ({impl})",
        )
        np.testing.assert_array_equal(
            np.asarray(got.cell_count), np.asarray(want.cell_count),
            err_msg=f"cell_count diverged ({impl})",
        )
        np.testing.assert_array_equal(
            np.asarray(got.slot_of_agent), np.asarray(want.slot_of_agent),
            err_msg=f"slot_of_agent diverged ({impl})",
        )
        assert bool(got.overflowed) == bool(want.overflowed), impl


def test_build_parity_random_pools_with_overflow():
    """max_per_cell=2 over dense pools: many cells overflow; the truncated
    cell list must still pick the same (lowest-index) agents per slot."""
    spec = spec_for_space(0.0, 20.0, 4.0, max_per_cell=2)
    for seed in range(5):
        rng = np.random.default_rng(seed)
        c = int(rng.integers(3, 97))
        position = jnp.asarray(rng.uniform(0, 20, (c, 3)), jnp.float32)
        alive = jnp.asarray(rng.random(c) < 0.8)
        _assert_build_parity(spec, position, alive)


def test_build_parity_all_dead():
    spec = spec_for_space(0.0, 10.0, 2.0, max_per_cell=4)
    rng = np.random.default_rng(7)
    position = jnp.asarray(rng.uniform(0, 10, (33, 3)), jnp.float32)
    _assert_build_parity(spec, position, jnp.zeros((33,), bool))


def test_build_parity_single_agent():
    spec = spec_for_space(0.0, 10.0, 2.0, max_per_cell=4)
    position = jnp.asarray([[3.0, 4.0, 5.0]], jnp.float32)
    _assert_build_parity(spec, position, jnp.ones((1,), bool))
    _assert_build_parity(spec, position, jnp.zeros((1,), bool))


def test_build_parity_ghost_extended():
    """The distributed engine's build: a halo-extended spec over local +
    ghost rows (ghosts land in the boundary cells, some ghost slots dead) —
    the exact input shape of distributed.dist_env_build_op."""
    from repro.core.distributed import DomainConfig

    dcfg = DomainConfig(
        mesh_axes=("x", "y"), axis_sizes=(2, 2), extent=30.0,
        halo_width=3.0, halo_capacity=16, migrate_capacity=8, depth=30.0,
    )
    spec = dcfg.grid_spec(box_size=3.0, max_per_cell=3)
    for seed in range(3):
        rng = np.random.default_rng(100 + seed)
        local = rng.uniform(0.0, 30.0, (64, 3))
        ghosts = rng.uniform(0.0, 30.0, (64, 3))
        # Push ghost rows into the aura bands of the decomposed dims.
        for d in range(2):
            band = rng.random(64) < 0.5
            ghosts[band, d] = rng.uniform(-3.0, 0.0, int(band.sum()))
            ghosts[~band, d] = rng.uniform(30.0, 33.0, int((~band).sum()))
        position = jnp.asarray(
            np.concatenate([local, ghosts]), jnp.float32
        )
        alive = jnp.asarray(rng.random(128) < 0.75)
        _assert_build_parity(spec, position, alive)


# ---------------------------------------------------------------------------
# Sort-free layout sort (ISSUE 8 tentpole a): sort_agents must reproduce the
# retired argsort permutation bit-exactly — same slot per agent, same tie
# order within a cell, dead agents compacted to the back.
# ---------------------------------------------------------------------------

def _assert_pool_equal(got, want, msg=""):
    np.testing.assert_array_equal(
        np.asarray(got.alive), np.asarray(want.alive), err_msg=f"alive {msg}"
    )
    np.testing.assert_array_equal(
        np.asarray(got.position), np.asarray(want.position),
        err_msg=f"position {msg}",
    )
    for field in ("diameter", "kind", "age", "static"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, field)), np.asarray(getattr(want, field)),
            err_msg=f"{field} {msg}",
        )
    assert got.attrs.keys() == want.attrs.keys()
    for name in want.attrs:
        np.testing.assert_array_equal(
            np.asarray(got.attrs[name]), np.asarray(want.attrs[name]),
            err_msg=f"attr {name} {msg}",
        )


def _assert_sort_parity(spec, pool, tile=16):
    want = sort_agents_argsort(spec, pool)
    for impl in ("xla", "pallas"):
        got = sort_agents(
            dataclasses.replace(spec, rank_impl=impl), pool, rank_tile=tile
        )
        _assert_pool_equal(got, want, msg=f"({impl})")


def _random_attr_pool(rng, n, cap, lo, hi):
    position = rng.uniform(lo, hi, (n, 3)).astype(np.float32)
    pool = make_pool(
        cap,
        jnp.asarray(position),
        diameter=jnp.asarray(rng.uniform(1.0, 4.0, n).astype(np.float32)),
        kind=jnp.asarray(rng.integers(0, 3, n).astype(np.int32)),
        attrs={"tag": jnp.asarray(np.arange(n, dtype=np.int32))},
    )
    # Kill a random subset so dead agents are interleaved, not just padding.
    dead = jnp.asarray(rng.random(cap) < 0.3)
    return pool.replace(alive=pool.alive & ~dead)


def test_sort_parity_random_pools():
    for seed in range(4):
        rng = np.random.default_rng(seed)
        spec = spec_for_space(0.0, 20.0, 4.0, max_per_cell=4)
        pool = _random_attr_pool(rng, int(rng.integers(5, 90)), 96, 0.0, 20.0)
        _assert_sort_parity(spec, pool)


def test_sort_parity_linear_layout():
    rng = np.random.default_rng(11)
    spec = spec_for_space(0.0, 20.0, 4.0, max_per_cell=4, use_morton=False)
    pool = _random_attr_pool(rng, 70, 96, 0.0, 20.0)
    _assert_sort_parity(spec, pool)


def test_sort_parity_overflowing_cells():
    """Sorting is independent of max_per_cell; a pool far over capacity per
    cell still permutes identically (overflow only truncates the *build*)."""
    rng = np.random.default_rng(5)
    spec = spec_for_space(0.0, 8.0, 4.0, max_per_cell=2)  # 2×2×2 cells
    pool = _random_attr_pool(rng, 60, 64, 0.0, 8.0)
    _assert_sort_parity(spec, pool)


def test_sort_parity_all_dead():
    rng = np.random.default_rng(6)
    spec = spec_for_space(0.0, 10.0, 2.0, max_per_cell=4)
    pool = _random_attr_pool(rng, 33, 48, 0.0, 10.0)
    pool = pool.replace(alive=jnp.zeros((48,), bool))
    _assert_sort_parity(spec, pool)


def test_sort_parity_ghost_extended_spec():
    """The halo-extended spec of the distributed engine: origin below the
    local domain, positions spilling into the aura bands."""
    from repro.core.distributed import DomainConfig

    dcfg = DomainConfig(
        mesh_axes=("x", "y"), axis_sizes=(2, 2), extent=30.0,
        halo_width=3.0, halo_capacity=16, migrate_capacity=8, depth=30.0,
    )
    spec = dcfg.grid_spec(box_size=3.0, max_per_cell=3)
    rng = np.random.default_rng(42)
    pool = _random_attr_pool(rng, 100, 128, -3.0, 33.0)
    _assert_sort_parity(spec, pool)


def test_sorted_fast_path_build_parity():
    """After sort_agents, build_index_arrays(assume_sorted=True) (rank =
    row − cell_start, no cell_rank pass) must equal the argsort-oracle
    build on the same sorted arrays."""
    for seed, use_morton in [(0, True), (1, True), (2, False)]:
        rng = np.random.default_rng(seed)
        spec = spec_for_space(0.0, 20.0, 4.0, max_per_cell=3,
                              use_morton=use_morton)
        pool = _random_attr_pool(rng, 80, 96, 0.0, 20.0)
        pool = sort_agents(spec, pool)
        want = build_index_arrays_argsort(spec, pool.position, pool.alive)
        got = build_index_arrays(
            spec, pool.position, pool.alive, assume_sorted=True
        )
        np.testing.assert_array_equal(
            np.asarray(got.cell_of_agent), np.asarray(want.cell_of_agent)
        )
        np.testing.assert_array_equal(
            np.asarray(got.cell_list), np.asarray(want.cell_list)
        )
        np.testing.assert_array_equal(
            np.asarray(got.cell_count), np.asarray(want.cell_count)
        )
        np.testing.assert_array_equal(
            np.asarray(got.slot_of_agent), np.asarray(want.slot_of_agent)
        )
        assert bool(got.overflowed) == bool(want.overflowed)


@pytest.mark.parametrize("build", ["cell_rank", "assume_sorted", "oracle"])
def test_slot_of_agent_inverts_cell_list(build):
    """``slot_of_agent`` is each listed agent's flat slot in the cell list,
    and n_cells·M for dead agents and agents dropped from a full cell — the
    same from both build branches and from the argsort oracle."""
    rng = np.random.default_rng(5)
    spec = spec_for_space(0.0, 20.0, 4.0, max_per_cell=2)
    pool = sort_agents(spec, _random_attr_pool(rng, 80, 96, 0.0, 20.0))
    want = build_index_arrays_argsort(spec, pool.position, pool.alive)
    if build == "oracle":
        index = want
    else:
        index = build_index_arrays(
            spec, pool.position, pool.alive,
            assume_sorted=build == "assume_sorted",
        )
    np.testing.assert_array_equal(
        np.asarray(index.slot_of_agent), np.asarray(want.slot_of_agent)
    )

    assert index.slot_of_agent.dtype == jnp.int32
    slots = np.asarray(index.slot_of_agent)
    flat = np.asarray(index.cell_list).reshape(-1)
    sentinel = flat.size
    listed = slots < sentinel
    ids = np.arange(slots.size)
    np.testing.assert_array_equal(flat[slots[listed]], ids[listed])
    assert set(ids[listed]) == set(flat[flat < slots.size])
    alive = np.asarray(pool.alive)
    assert np.all(slots[~alive] == sentinel)
    dropped = alive & ~listed
    assert bool(index.overflowed) and dropped.any()
    assert np.all(slots[dropped] == sentinel)


def test_sort_agents_lowers_without_hlo_sort():
    """The zero-sort guarantee itself, asserted at the unit level: the
    jitted layout sort contains no HLO sort op (the argsort fallback only
    engages past morton.MAX_TABLE_CELLS)."""
    import jax

    spec = spec_for_space(0.0, 20.0, 4.0, max_per_cell=4)
    rng = np.random.default_rng(3)
    pool = _random_attr_pool(rng, 50, 64, 0.0, 20.0)
    hlo = jax.jit(lambda p: sort_agents(spec, p)).lower(pool).as_text()
    assert hlo.count("sort(") == 0, "layout sort still lowers an HLO sort"


# ---------------------------------------------------------------------------
# Rank-primitive properties (ISSUE 5 satellite; runs on the real hypothesis
# engine when installed, on the bundled executor otherwise — never skips).
# ---------------------------------------------------------------------------

@settings(deadline=None, max_examples=15)
@given(
    c=st.integers(1, 120),
    n_cells=st.integers(1, 200),
    seed=st.integers(0, 2**31 - 1),
    impl=st.sampled_from(["xla", "pallas"]),
)
def test_cell_rank_bijection_property(c, n_cells, seed, impl):
    """Per cell, ranks are a bijection onto 0..count-1 — and stable: in
    index order they are exactly arange(count)."""
    rng = np.random.default_rng(seed)
    cid = rng.integers(0, n_cells + 1, c)          # sentinel value included
    rank = np.asarray(
        cr_ops.cell_rank(jnp.asarray(cid, jnp.int32), n_cells=n_cells,
                         impl=impl, tile=32)
    )
    for v in np.unique(cid):
        group = rank[cid == v]
        np.testing.assert_array_equal(group, np.arange(group.size))


@settings(deadline=None, max_examples=15)
@given(
    n=st.integers(0, 90),
    seed=st.integers(0, 2**31 - 1),
    max_per_cell=st.sampled_from([1, 3, 8]),
)
def test_build_counts_match_histogram_property(n, seed, max_per_cell):
    """cell_count equals the plain histogram of live agents' cell ids, and
    dead agents are excluded everywhere (sentinel cell id, no cell_list
    slot, no count)."""
    cap = 96
    rng = np.random.default_rng(seed)
    spec = spec_for_space(0.0, 24.0, 4.0, max_per_cell=max_per_cell)
    position = jnp.asarray(rng.uniform(0, 24, (cap, 3)), jnp.float32)
    alive_np = np.zeros(cap, bool)
    alive_np[rng.choice(cap, size=n, replace=False)] = True
    index = build_index_arrays(spec, position, jnp.asarray(alive_np))

    cid = np.asarray(index.cell_of_agent)
    assert (cid[~alive_np] == spec.n_cells).all()
    hist = np.bincount(cid[alive_np], minlength=spec.n_cells + 1)[: spec.n_cells]
    np.testing.assert_array_equal(np.asarray(index.cell_count), hist)
    assert int(index.cell_count.sum()) == n

    listed = np.asarray(index.cell_list).reshape(-1)
    listed = listed[listed < cap]
    assert alive_np[listed].all(), "dead agent leaked into the cell list"
    assert len(set(listed.tolist())) == listed.size
