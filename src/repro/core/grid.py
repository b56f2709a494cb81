"""Uniform-grid environment: fixed-radius neighbor search (§5.3.1).

BioDynaMo's UniformGridEnvironment divides space into boxes of edge length
``box_size`` (≥ the interaction radius) and stores each box's agents in an
array-based linked list, rebuilt in O(#agents) per iteration via timestamps.

TPU adaptation (see DESIGN.md):
  * build = rank + scatter, no sort.  Each agent's rank within its cell
    comes from a sort-free tiled-histogram pass
    (`repro.kernels.cell_rank`: per-tile per-cell counts → exclusive scan
    over tiles → intra-tile ranks — the `agents.compact_indices` cumsum-rank
    idiom generalized to a multi-valued key), the TPU analogue of the
    paper's timestamped O(#agents) build.  The §5.4.2 agent-*sorting*
    optimization is a separate, frequency-gated layout op
    (:func:`sort_agents`) — the only sort anywhere in the step.
  * linked list = cell list.  A dense ``(n_cells, max_per_cell)`` index tensor
    replaces pointer chasing: deterministic ranks (position-in-run) scatter
    each agent into its cell row.  Overflow is detected, not UB.
  * query = 27-box gather.  Fixed-radius neighbor candidates are the 3×3×3
    box neighborhood, a static-shape gather of ``27 * max_per_cell`` slots.

The returned :class:`GridIndex` is a pytree so it can flow through jit/scan.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.cell_rank import ops as cr_ops

from . import morton
from .agents import AgentPool, permute, permute_to

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GridSpec:
    """Static description of the uniform grid (metadata, not traced)."""

    origin: Tuple[float, float, float] = dataclasses.field(metadata=dict(static=True))
    box_size: float = dataclasses.field(metadata=dict(static=True))
    dims: Tuple[int, int, int] = dataclasses.field(metadata=dict(static=True))
    max_per_cell: int = dataclasses.field(metadata=dict(static=True))
    use_morton: bool = dataclasses.field(metadata=dict(static=True), default=True)
    # Within-cell ranking impl for the build stage (cr_ops.IMPLS), selected
    # like EngineConfig.force_impl: "xla" is the pure-XLA tiled-histogram
    # pass (the default, and the only one that compiles for TPU today),
    # "pallas" the repro.kernels.cell_rank VMEM-histogram kernel, whose
    # (L, 1) block Mosaic does not accept yet.
    rank_impl: str = dataclasses.field(metadata=dict(static=True), default="xla")

    def __post_init__(self):
        if self.rank_impl not in cr_ops.IMPLS:
            raise ValueError(
                f"unknown rank_impl {self.rank_impl!r}; expected one of "
                f"{cr_ops.IMPLS}"
            )

    @property
    def n_cells(self) -> int:
        nx, ny, nz = self.dims
        return nx * ny * nz


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GridIndex:
    """Built neighbor index over one agent pool.

    cell_of_agent: (C,)  int32 — linear cell id per agent (dead → n_cells).
    cell_list:     (n_cells, M) int32 — agent index per slot, C where empty.
    cell_count:    (n_cells,) int32 — #agents per cell (may exceed M; overflow).
    overflowed:    ()   bool — any cell exceeded max_per_cell.
    slot_of_agent: (C,)  int32 — each agent's flat slot in
                   ``cell_list.reshape(-1)``; n_cells·M for dead agents and
                   agents dropped from an overflowing cell.
    """

    cell_of_agent: Array
    cell_list: Array
    cell_count: Array
    overflowed: Array
    slot_of_agent: Array


def cell_coords(spec: GridSpec, position: Array) -> Array:
    """(N,3) float positions → (N,3) int32 cell coordinates, clipped to grid."""
    origin = jnp.asarray(spec.origin, jnp.float32)
    rel = (position - origin) / jnp.float32(spec.box_size)
    ijk = jnp.floor(rel).astype(jnp.int32)
    dims = jnp.asarray(spec.dims, jnp.int32)
    return jnp.clip(ijk, 0, dims - 1)


def linear_cell_id(spec: GridSpec, ijk: Array) -> Array:
    nx, ny, nz = spec.dims
    return (ijk[..., 0] * ny + ijk[..., 1]) * nz + ijk[..., 2]


def sort_key(spec: GridSpec, ijk: Array) -> Array:
    """Sort key per agent: Morton code (default) or row-major linear id."""
    if spec.use_morton:
        return morton.encode3(
            ijk[..., 0].astype(jnp.uint32),
            ijk[..., 1].astype(jnp.uint32),
            ijk[..., 2].astype(jnp.uint32),
        ).astype(jnp.uint32)
    return linear_cell_id(spec, ijk).astype(jnp.uint32)


def layout_rank_table(spec: GridSpec) -> Array:
    """(n_cells + 1,) int32: linear cell id → rank in layout (Z-)order.

    Slot ``n_cells`` is the dead-agent bin and ranks last.  The table is a
    host-computed constant (the grid shape is static), so consuming it costs
    no HLO sort.
    """
    zrank = morton.cell_zrank(spec.dims, spec.use_morton)
    return jnp.asarray(
        jnp.concatenate(
            [jnp.asarray(zrank, jnp.int32), jnp.asarray([spec.n_cells], jnp.int32)]
        )
    )


def sort_agents(
    spec: GridSpec,
    pool: AgentPool,
    rank_tile: int | None = None,
) -> AgentPool:
    """§5.4.2 agent sorting: reorder the pool along the space-filling curve.

    Dead agents sort to the back (key = max), which doubles as the paper's
    §5.3.2 compaction.

    Sort-free: instead of a stable argsort on the Morton key, the permutation
    is assembled counting-sort style from the `kernels/cell_rank`
    tiled-histogram machinery — per-cell counts, an exclusive scan over cells
    *in Z-order* (a trace-time table, since the grid is static), and each
    agent's index-order rank within its cell:

        dest[i] = z_offset[cell[i]] + rank_within_cell[i]

    which is exactly the slot a stable argsort on the Morton key would give
    agent ``i`` (the Z-rank of a cell is strictly monotone in its Morton code,
    and stable ties break in index order — precisely ``cell_rank``).  The pool
    is then scattered with :func:`repro.core.agents.permute_to`.  Zero HLO
    sorts, so enabling ``sort_frequency=1`` keeps the whole-step zero-sort
    guarantee.  Bit-exactness vs the retired argsort is pinned by
    ``tests/grid_oracle.sort_agents_argsort``.

    Grids too large for the trace-time Z-rank table fall back to the argsort.
    """
    if spec.n_cells > morton.MAX_TABLE_CELLS:
        ijk = cell_coords(spec, pool.position)
        key = sort_key(spec, ijk)
        key = jnp.where(pool.alive, key, jnp.uint32(0xFFFFFFFF))
        perm = jnp.argsort(key, stable=True)
        return permute(pool, perm)

    n_cells = spec.n_cells
    ijk = cell_coords(spec, pool.position)
    cid = jnp.where(pool.alive, linear_cell_id(spec, ijk), n_cells)  # (C,)
    zid = layout_rank_table(spec)[cid]  # rank of the agent's cell in Z-order

    rank = cr_ops.cell_rank(
        zid,
        n_cells=n_cells,
        impl=spec.rank_impl,
        tile=rank_tile,
    )
    counts = jnp.zeros((n_cells + 1,), jnp.int32).at[zid].add(1)
    offsets = jnp.cumsum(counts) - counts  # exclusive scan in Z-order
    dest = offsets[zid] + rank
    return permute_to(pool, dest)


def cell_starts_sorted(spec: GridSpec, cell_count: Array) -> tuple[Array, Array]:
    """Per-cell [start, end) row ranges of a layout-sorted pool.

    Given per-cell live counts, returns ``(start, end)``, both ``(n_cells,)``
    int32: when the pool is sorted along the layout curve (dead at the back),
    the live agents of linear cell ``c`` occupy rows ``start[c]:end[c]``.
    Pure O(n_cells) table arithmetic — no sort.
    """
    order = jnp.asarray(morton.zorder_cells(spec.dims, spec.use_morton))
    zcounts = cell_count[order]
    zstarts = jnp.cumsum(zcounts) - zcounts  # exclusive scan in layout order
    start = jnp.zeros_like(cell_count).at[order].set(zstarts)
    return start, start + cell_count


def build_index_arrays(
    spec: GridSpec,
    position: Array,
    alive: Array,
    rank_tile: int | None = None,
    assume_sorted: bool = False,
) -> GridIndex:
    """Build the cell list (the §5.3.1 'build stage'), fully parallel.

    ``position``/``alive`` may be a ghost-extended superset of the local pool
    (the distributed engine indexes local + halo agents together; halo agents
    land in the boundary cells of the halo-extended ``spec``, which is what
    lets the fused cell-list force kernel consume this index unchanged —
    DESIGN.md §4).

    Steps — sort-free, the TPU analogue of the paper's timestamped
    O(#agents) build (no O(C log C) component anywhere; the seed's per-step
    stable argsort survives only as the test oracle in tests/grid_oracle.py):
      1. cell id per agent (O(C));
      2. rank of each agent within its cell, via the tiled-histogram pass of
         `repro.kernels.cell_rank` (per-tile per-cell counts → exclusive
         scan over tiles → intra-tile ranks; impl per ``spec.rank_impl``);
      3. scatter agent indices into ``cell_list[cell, rank]`` (O(C)); the
         flat slot ``cell·M + rank`` is kept as ``slot_of_agent``, the
         inverse map the fused force pass gathers through.

    ``rank_tile`` overrides the ≈√n_cells rank tile (tests keep
    interpret-mode grids coarse with it).

    ``assume_sorted`` promises the arrays are already layout-sorted — i.e.
    :func:`sort_agents` ran on this exact pool with this exact spec and
    nothing reordered or moved agents since (true on the single-node engine
    at ``sort_frequency=1``; never true distributed, where migrate/halo run
    between sort and build).  The within-cell rank is then just
    ``row − cell_start`` (:func:`cell_starts_sorted`), skipping the
    tiled-histogram ``cell_rank`` pass entirely — the §5.4.2 payoff where a
    sorted layout makes the build as cheap as the paper's timestamped one.
    """
    c = position.shape[0]
    n_cells = spec.n_cells
    ijk = cell_coords(spec, position)
    cid = jnp.where(alive, linear_cell_id(spec, ijk), n_cells)  # (C,)

    counts = jnp.zeros((n_cells + 1,), jnp.int32).at[cid].add(1)
    cell_count = counts[:n_cells]

    if assume_sorted:
        start, _ = cell_starts_sorted(spec, cell_count)
        start_ext = jnp.concatenate([start, jnp.zeros((1,), jnp.int32)])
        rank = jnp.arange(c, dtype=jnp.int32) - start_ext[cid]
    else:
        rank = cr_ops.cell_rank(
            cid,
            n_cells=n_cells,
            impl=spec.rank_impl,
            tile=rank_tile,
        )
    overflowed = jnp.any(cell_count > spec.max_per_cell)

    # Scatter into the dense cell list (drop overflow + dead).
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


def build_index(
    spec: GridSpec,
    pool: AgentPool,
    rank_tile: int | None = None,
    assume_sorted: bool = False,
) -> GridIndex:
    return build_index_arrays(
        spec,
        pool.position,
        pool.alive,
        rank_tile=rank_tile,
        assume_sorted=assume_sorted,
    )


_NEIGHBOR_OFFSETS = jnp.asarray(
    [(dx, dy, dz) for dx in (-1, 0, 1) for dy in (-1, 0, 1) for dz in (-1, 0, 1)],
    jnp.int32,
)  # (27, 3)


def neighbor_cell_ids(spec: GridSpec, position: Array) -> tuple[Array, Array]:
    """27-box stencil cells for each query position.

    Returns ``(nbr_cid, in_range)``: ``(N, 27)`` linear cell ids (clipped
    into the grid — consult ``in_range`` before trusting a slot) and the
    ``(N, 27)`` validity mask.  The single definition of the stencil shared
    by candidate generation and the cell-level static detection.
    """
    dims = jnp.asarray(spec.dims, jnp.int32)
    nbr = cell_coords(spec, position)[:, None, :] + _NEIGHBOR_OFFSETS[None, :, :]
    in_range = jnp.all((nbr >= 0) & (nbr < dims), axis=-1)
    nbr_cid = linear_cell_id(spec, jnp.clip(nbr, 0, dims - 1))
    return nbr_cid, in_range


def candidate_neighbors_arrays(
    spec: GridSpec,
    index: GridIndex,
    query_position: Array,
    query_alive: Array,
    query_ids: Array | None = None,
) -> tuple[Array, Array]:
    """For every query agent, gather candidate neighbor ids (27-box stencil).

    ``index`` may have been built over a *superset* of the queries (e.g. local
    + halo agents in the distributed engine); ``query_ids`` gives each query's
    own index in that superset so self-pairs are excluded (defaults to
    ``arange`` — queries are the indexed set itself).

    Returns ``(cand, mask)``: ``cand (N, 27*M) int32`` into the indexed set
    (out-of-range slots = indexed-set capacity), ``mask (N, 27*M) bool``.
    """
    n = query_position.shape[0]
    m = spec.max_per_cell
    nbr_cid, in_range = neighbor_cell_ids(spec, query_position)  # (N, 27)

    cand = index.cell_list[nbr_cid]                              # (N, 27, M)
    sentinel = index.cell_of_agent.shape[0]                      # indexed capacity
    valid = in_range[:, :, None] & (cand < sentinel)             # (N, 27, M)
    cand = jnp.where(valid, cand, sentinel)
    cand = cand.reshape(n, 27 * m)
    valid = valid.reshape(n, 27 * m)
    if query_ids is None:
        query_ids = jnp.arange(n, dtype=jnp.int32)
    not_self = cand != query_ids[:, None]
    mask = valid & not_self & query_alive[:, None]
    return cand, mask


def candidate_neighbors(spec: GridSpec, index: GridIndex, pool: AgentPool) -> tuple[Array, Array]:
    """Candidate neighbors of every agent in the pool (mask: valid ∧ ¬self)."""
    return candidate_neighbors_arrays(spec, index, pool.position, pool.alive)


def spec_for_space(
    min_bound: float,
    max_bound: float,
    interaction_radius: float,
    max_per_cell: int = 16,
    use_morton: bool = True,
    rank_impl: str = "xla",
) -> GridSpec:
    """Convenience: cubic simulation space with box size = interaction radius.

    Mirrors BioDynaMo's automatic box sizing: boxes at least as large as the
    largest interaction radius so the 27-box stencil is sufficient.
    """
    extent = float(max_bound - min_bound)
    n = max(int(extent / interaction_radius), 1)
    n = min(n, morton.max_grid_dim())
    box = extent / n
    return GridSpec(
        origin=(min_bound, min_bound, min_bound),
        box_size=box,
        dims=(n, n, n),
        max_per_cell=max_per_cell,
        use_morton=use_morton,
        rank_impl=rank_impl,
    )
