"""Test-only oracle: the seed's argsort-based grid build (§5.3.1).

This is the implementation `repro.core.grid.build_index_arrays` replaced —
within-cell ranks derived from a stable ``argsort(cid)`` (O(C log C), the
last per-step sort on the hot path).  It survives here, verbatim, as the
bit-exactness reference for the sort-free tiled-histogram build: the parity
suite in test_grid.py asserts identical ``cell_list`` / ``cell_count`` /
``cell_of_agent`` / ``overflowed`` across randomized pools.  Never import
this from ``src`` — reintroducing it on the hot path is exactly what the
whole-step zero-sort lowering guards (bench_fused_force / bench_dist_fused)
exist to catch.
"""

import jax
import jax.numpy as jnp

from repro.core.agents import AgentPool, permute
from repro.core.grid import (
    GridIndex,
    GridSpec,
    cell_coords,
    linear_cell_id,
    sort_key,
)


def sort_agents_argsort(spec: GridSpec, pool: AgentPool) -> AgentPool:
    """The retired argsort-backed §5.4.2 layout sort, kept bit-for-bit.

    The sort-free ``grid.sort_agents`` (counting-sort permutation from the
    cell_rank histogram machinery) must reproduce this pool exactly —
    including tie order among agents of one cell and dead-agents-to-the-back
    compaction.
    """
    ijk = cell_coords(spec, pool.position)
    key = sort_key(spec, ijk)
    key = jnp.where(pool.alive, key, jnp.uint32(0xFFFFFFFF))
    perm = jnp.argsort(key, stable=True)
    return permute(pool, perm)


def build_index_arrays_argsort(
    spec: GridSpec, position: jax.Array, alive: jax.Array
) -> GridIndex:
    """The historical sort-based build stage, kept bit-for-bit."""
    c = position.shape[0]
    n_cells = spec.n_cells
    ijk = cell_coords(spec, position)
    cid = jnp.where(alive, linear_cell_id(spec, ijk), n_cells)  # (C,)

    # Rank within cell: sort agent ids by cell, positions within equal-cid runs
    # give ranks; then scatter ranks back to agent order.
    order = jnp.argsort(cid, stable=True)                  # agent ids, cell-grouped
    sorted_cid = cid[order]
    # start-of-run marker → rank = position - start_of_run_position.
    pos = jnp.arange(c, dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_cid[1:] != sorted_cid[:-1]]
    )
    run_start = jax.lax.cummax(jnp.where(is_start, pos, -1))
    rank_sorted = pos - run_start                          # rank within cell
    rank = jnp.zeros((c,), jnp.int32).at[order].set(rank_sorted)

    counts = jnp.zeros((n_cells + 1,), jnp.int32).at[cid].add(1)
    cell_count = counts[:n_cells]
    overflowed = jnp.any(cell_count > spec.max_per_cell)

    m = spec.max_per_cell
    valid = alive & (rank < m)
    flat_idx = jnp.where(valid, cid * m + rank, n_cells * m)
    cell_list = jnp.full((n_cells * m + 1,), c, jnp.int32)
    cell_list = cell_list.at[flat_idx].set(
        jnp.arange(c, dtype=jnp.int32), mode="drop"
    )[: n_cells * m].reshape(n_cells, m)

    return GridIndex(
        cell_of_agent=cid.astype(jnp.int32),
        cell_list=cell_list,
        cell_count=cell_count,
        overflowed=overflowed,
        slot_of_agent=flat_idx,
    )
