"""Mechanical contact forces between spherical agents (§4.5.1, Eq 4.1).

    F_N = k·δ − γ·√(r̄·δ),   δ = r₁ + r₂ − |x₁ − x₂|,   r̄ = r₁r₂/(r₁+r₂)

applied along the center line when agents overlap (δ > 0).  This is the
dominant operation of the paper's benchmarks (§5.6.3: "mechanical forces"
takes the largest share of runtime), hence it is the Pallas-kernel hot spot:
`repro.kernels.pairwise_force` fuses the force arithmetic over dense
candidates, and `repro.kernels.cell_force` (``impl="fused"``) additionally
eliminates the dense candidate tensor by walking the cell list directly
(DESIGN.md §4).

Static-agent force omission (§5.5): the paper detects agents whose resulting
force is guaranteed zero-displacement (agent and its whole neighborhood did
not move last iteration) and skips them.  TPUs cannot early-exit a SIMD lane,
so the adaptation is *work compaction*: gather the indices of non-static
agents into a bounded active set and evaluate forces only for that set,
scattering results back.  FLOPs then scale with the number of moving agents,
which is the paper's intent.  When the active set overflows its bound we fall
back to evaluating everything (correctness first).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from .. import spans
from .agents import AgentPool, compact_indices
from .grid import _NEIGHBOR_OFFSETS, GridIndex, GridSpec, neighbor_cell_ids
from .neighbors import NeighborContext

Array = jax.Array

FORCE_IMPLS = ("reference", "pallas", "fused")
TILE_ORDERS = ("linear", "morton")


def _morton_window_ok(
    spec: GridSpec,
    index: GridIndex,
    block: int | None,
    window: int | None,
) -> Array:
    """() bool: may this step run the Morton-window force kernel exactly?

    The window kernel is exact iff every live agent's 27-box neighbors all
    sit within ``± half_window`` storage blocks of its own row.  Checked
    from the *actual* rows (per-cell min/max row via scatter, O(C + 27C)),
    not from an assumed-sorted layout — an unsorted or half-sorted pool
    simply fails the check and takes the fallback, it can never produce a
    wrong force.  Uses the same stale cell ids as the kernels, so the pair
    set being certified is exactly the one the kernel computes.
    """
    from repro.kernels.cell_force import ops as cf_ops

    cid = index.cell_of_agent
    c = cid.shape[0]
    bw, h = cf_ops.window_defaults(c, block, window)
    n_cells = spec.n_cells
    nx, ny, nz = spec.dims

    rows = jnp.arange(c, dtype=jnp.int32)
    live = cid < n_cells
    big = jnp.int32(c)
    rmin = jnp.full((n_cells + 1,), big, jnp.int32).at[cid].min(rows)
    rmax = jnp.full((n_cells + 1,), -1, jnp.int32).at[cid].max(rows)

    ijk = jnp.stack([cid // (ny * nz), (cid // nz) % ny, cid % nz], axis=-1)
    nbr = ijk[:, None, :] + _NEIGHBOR_OFFSETS[None, :, :]        # (C, 27, 3)
    dims = jnp.asarray(spec.dims, jnp.int32)
    in_range = jnp.all((nbr >= 0) & (nbr < dims), axis=-1)
    ncid = (nbr[..., 0] * ny + nbr[..., 1]) * nz + nbr[..., 2]
    ncid = jnp.clip(ncid, 0, n_cells - 1)
    nmn = jnp.min(jnp.where(in_range, rmin[ncid], big), axis=1)  # (C,)
    nmx = jnp.max(jnp.where(in_range, rmax[ncid], -1), axis=1)

    blk = rows // bw
    lo = (blk - h) * bw
    hi = (blk + h + 1) * bw
    return jnp.all(~live | ((nmn >= lo) & (nmx < hi)))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ForceParams:
    """Eq 4.1 parameters.  BioDynaMo/Cortex3D defaults: k=2, γ=1."""

    repulsion_k: float = dataclasses.field(metadata=dict(static=True), default=2.0)
    attraction_gamma: float = dataclasses.field(metadata=dict(static=True), default=1.0)
    # Displacement below this (per iteration) marks an agent "not moved" for
    # the §5.5 static-agent detection.
    static_tolerance: float = dataclasses.field(metadata=dict(static=True), default=1e-4)


def pair_force(
    dx: Array, r1: Array, r2: Array, params: ForceParams
) -> Array:
    """Force on agent 1 from agent 2.  dx = x1 - x2, shape (..., 3)."""
    # Explicit left-associated squared distance — NOT jnp.sum(dx*dx, -1).
    # A reduce's accumulation order is implementation-defined and XLA:CPU
    # picks it per fusion context, so the same pass embedded in two
    # differently-shaped programs (serial vs overlapped distributed
    # schedules) can disagree by 1 ulp.  Explicit adds pin the association
    # in the graph — and match the cell_force kernel's formulation, keeping
    # dense↔fused parity bit-exact.
    d2 = dx[..., 0] * dx[..., 0] + dx[..., 1] * dx[..., 1] + dx[..., 2] * dx[..., 2]
    dist = jnp.sqrt(d2 + 1e-20)
    delta = r1 + r2 - dist
    overlap = delta > 0.0
    rbar = r1 * r2 / jnp.maximum(r1 + r2, 1e-20)
    magnitude = (
        params.repulsion_k * delta
        - params.attraction_gamma * jnp.sqrt(jnp.maximum(rbar * delta, 0.0))
    )
    direction = dx / dist[..., None]
    return jnp.where(overlap[..., None], magnitude[..., None] * direction, 0.0)


def _tree_sum(f: Array) -> Array:
    """Fixed-association pairwise sum over axis 1.

    ``jnp.sum``'s accumulation order is implementation-defined per fusion
    context on XLA:CPU; two differently-shaped programs embedding the same
    candidate reduction can disagree by 1 ulp — breaking the
    serial↔overlapped distributed bit-exactness contract.  An explicit
    balanced add-tree pins the association in the HLO graph itself (strict
    IEEE adds are never reassociated), at the same O(N·K) cost."""
    k = f.shape[1]
    while k > 1:
        half = k // 2
        s = f[:, :half] + f[:, half:2 * half]
        if k % 2:
            s = jnp.concatenate([s, f[:, 2 * half:]], axis=1)
        f = s
        k = (k + 1) // 2
    return f[:, 0]


def forces_from_candidates(
    position: Array,
    radius: Array,
    cand: Array,
    cand_mask: Array,
    params: ForceParams,
    all_position: Optional[Array] = None,
    all_radius: Optional[Array] = None,
) -> Array:
    """Sum Eq-4.1 forces over each agent's candidate neighbor set.

    position/radius: (N, 3)/(N,) query agents.
    cand:            (N, K) int32 indices into the *full* pool.
    cand_mask:       (N, K) bool.
    all_position/all_radius: full pool arrays to gather candidates from
                     (default: same as query arrays).
    """
    src_pos = position if all_position is None else all_position
    src_rad = radius if all_radius is None else all_radius
    safe = jnp.where(cand_mask, cand, 0)
    npos = jnp.take(src_pos, safe, axis=0)                 # (N, K, 3)
    nrad = jnp.take(src_rad, safe, axis=0)                 # (N, K)
    dx = position[:, None, :] - npos                       # (N, K, 3)
    f = pair_force(dx, radius[:, None], nrad, params)      # (N, K, 3)
    f = jnp.where(cand_mask[:, :, None], f, 0.0)
    return _tree_sum(f)                                    # (N, 3)


def forces_from_candidates_tiled(
    position: Array,
    radius: Array,
    cand: Array,
    cand_mask: Array,
    params: ForceParams,
    all_position: Array,
    all_radius: Array,
    tile: int,
    unroll: bool = True,
) -> Array:
    """Tile-wise force evaluation (§Perf teraagent iteration).

    The dense path materializes the full (N, K, 3) candidate gather plus
    ~four (N, K) force intermediates — ~36 GB at N=1M, K=864.  Mapping over
    agent tiles bounds the working set to one tile's worth (the XLA-level
    analogue of the Pallas kernel's VMEM tiling; on real TPU the
    `pairwise_force` kernel eliminates the intermediates entirely).

    ``unroll=True`` (default) emits a python loop over tiles — correct
    cost_analysis accounting (while-loop bodies are counted once) and the
    scheduler still reuses one tile's buffers; ``unroll=False`` uses
    ``lax.map`` (smaller HLO for very large tile counts)."""
    n = position.shape[0]
    pad = (-n) % tile
    padz = lambda x: jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
    pos_t = padz(position).reshape(-1, tile, 3)
    rad_t = padz(radius).reshape(-1, tile)
    cand_t = padz(cand).reshape(-1, tile, cand.shape[1])
    mask_t = padz(cand_mask).reshape(-1, tile, cand.shape[1])

    def one(args):
        p, r, c, m = args
        return forces_from_candidates(
            p, r, c, m, params,
            all_position=all_position, all_radius=all_radius,
        )

    if unroll:
        outs = [one((pos_t[i], rad_t[i], cand_t[i], mask_t[i]))
                for i in range(pos_t.shape[0])]
        out = jnp.concatenate(outs, axis=0)
        return out[:n]
    out = jax.lax.map(one, (pos_t, rad_t, cand_t, mask_t))
    return out.reshape(-1, 3)[:n]


def mechanical_forces(
    spec: GridSpec,
    index: GridIndex,
    pool: AgentPool,
    params: ForceParams,
    active_capacity: Optional[int] = None,
    impl: str = "reference",
    neighbors: Optional[NeighborContext] = None,
    fused_fallback: bool = True,
    tile: Optional[int] = None,
    tile_order: str = "linear",
    morton_block: Optional[int] = None,
    morton_window: Optional[int] = None,
    morton_fallback: bool = True,
    row_mask: Optional[Array] = None,
) -> Array:
    """Net mechanical force per agent, (C, 3).

    ``row_mask``: optional (C,) bool — rows outside the mask get zero force
    in the output.  Pure *output* masking (the evaluation itself is
    unchanged, so a masked row's force is bit-identical to the unmasked
    call's): the overlapped distributed schedule dispatches the same pass
    twice with complementary interior/shell masks and merges by select,
    which must reproduce the single full pass bit-for-bit (DESIGN.md §4).

    active_capacity: if given, §5.5 work compaction — only agents with
    ``~pool.static`` are evaluated (bounded by this capacity; overflow falls
    back to the full evaluation).  ``impl`` selects "reference" (pure jnp),
    "pallas" (`repro.kernels.pairwise_force` over dense candidates), or
    "fused" (`repro.kernels.cell_force`, consuming ``index.cell_list``
    directly — no dense candidate tensor).

    ``neighbors``: the step's :class:`NeighborContext`; built here when
    absent (standalone calls), passed in by the engine so the dense
    candidate tensor is materialized at most once per iteration — and, on
    the fused path, not at all.  When the context's source arrays are a
    ghost-extended superset of the pool (the distributed engine, §6.2.1),
    all impls gather pair data from those sources; their local rows are
    refreshed to the pool's current (post-behavior) state, exactly what the
    single-node engine sees, while halo rows keep the exchange-time
    snapshot.  The fused kernel's slot forces are then gathered back for
    *local* rows only (ghost slots are never read) so the result stays
    (C, 3).

    ``fused_fallback`` guards the fused path's cell-list truncation: when
    any cell overflowed ``max_per_cell`` a ``lax.cond`` re-evaluates through
    the reference candidate path (correctness first, like the §5.5
    compaction fallback below).  The kernel impls pick Pallas interpret
    mode from the backend (:func:`repro.kernels.interpret_default`).
    ``tile``: evaluate the dense candidate path in agent tiles of this size
    (bounds the (tile, K, 3) working set; applies to the reference impl and
    the fused path's overflow fallback).

    ``tile_order="morton"`` (fused impl, single-node sources only): run the
    Morton-window kernel of `repro.kernels.cell_force` — storage-order tiles
    over the layout-sorted pool, each folding ``± morton_window`` contiguous
    blocks of ``morton_block`` agents (§5.4.2: the sorted layout turns the
    27-box gather into contiguous DMA).  Guarded per step by
    :func:`_morton_window_ok` ∧ no overflow; ``morton_fallback`` wraps that
    guard in a ``lax.cond`` to the linear fused path (bit-exact semantics
    whenever the window doesn't cover — set False only when the layout is
    known-sorted, e.g. the compile-cost benchmarks, since the cond bills
    both branches).  Ghost-extended sources always take the linear path:
    halo rows sit *appended* after the pool, never window-local to it.

    Combining ``impl="fused"`` with ``active_capacity`` composes: the
    compacted branch builds its candidate rows through
    :meth:`NeighborContext.candidates_for` — an ``(A, 27M)`` subset tensor
    for the active set only — so the dense ``(C, 27M)`` tensor appears
    nowhere outside the overflow-fallback branch and per-step neighbor
    traffic follows the number of *moving* agents, the paper's §5.5 intent.
    """
    if impl not in FORCE_IMPLS:
        raise ValueError(
            f"unknown force impl {impl!r}; expected one of {FORCE_IMPLS}"
        )
    if tile_order not in TILE_ORDERS:
        raise ValueError(
            f"unknown tile_order {tile_order!r}; expected one of {TILE_ORDERS}"
        )
    if neighbors is None:
        neighbors = NeighborContext.for_pool(spec, index, pool)
    radius = pool.radius()
    c = pool.capacity
    out_mask = pool.alive if row_mask is None else pool.alive & row_mask

    if neighbors.src_position.shape[0] == c:
        # Single-node: the sources ARE the pool — use its current arrays
        # (behaviors may have moved agents since the context was built).
        src_pos, src_rad = pool.position, radius
    else:
        # Ghost-extended sources (distributed): refresh the local rows to the
        # pool's current state; halo rows keep the exchange-time snapshot.
        src_pos = neighbors.src_position.at[:c].set(pool.position)
        src_rad = neighbors.src_radius.at[:c].set(radius)

    def dense_eval(cache: bool) -> Array:
        cand, mask = neighbors.candidates(cache=cache)
        if tile:
            return forces_from_candidates_tiled(
                pool.position, radius, cand, mask, params,
                src_pos, src_rad, tile=tile,
            )
        return forces_from_candidates(
            pool.position, radius, cand, mask, params,
            all_position=src_pos, all_radius=src_rad,
        )

    # Candidate-consuming impls always need the dense tensor somewhere in the
    # step; build (or reuse) it here, at top trace level, so consumers inside
    # lax.cond branches below read the cache instead of leaking a sub-trace
    # build.  The fused path skips this — its only candidate consumers live
    # inside the overflow-fallback branch and build uncached there, keeping
    # the dense tensor out of the non-overflow steady state.
    if impl != "fused":
        neighbors.candidates()

    if impl == "pallas":
        from repro.kernels.pairwise_force import ops as pf_ops

        dense = lambda: pf_ops.pairwise_force(
            pool.position, radius, *neighbors.candidates(),
            k=params.repulsion_k, gamma=params.attraction_gamma,
            all_position=src_pos, all_radius=src_rad,
        )
    elif impl == "fused":
        from repro.kernels.cell_force import ops as cf_ops

        fused = lambda: cf_ops.cell_list_force(
            src_pos, src_rad, index.cell_list, index.slot_of_agent,
            spec.dims, k=params.repulsion_k, gamma=params.attraction_gamma,
            num_out=c,
        )
        if tile_order == "morton" and src_pos is pool.position:
            morton_eval = lambda: cf_ops.cell_window_force(
                pool.position, radius, index.cell_of_agent, spec.dims,
                k=params.repulsion_k, gamma=params.attraction_gamma,
                block=morton_block, window=morton_window,
            )
            if morton_fallback:
                ok = _morton_window_ok(
                    spec, index, morton_block, morton_window
                ) & ~index.overflowed
                linear_fused = fused
                fused = lambda: jax.lax.cond(ok, morton_eval, linear_fused)
            else:
                fused = morton_eval
        if fused_fallback:

            def fallback():
                with jax.named_scope(spans.DENSE_FALLBACK):
                    return dense_eval(cache=False)

            dense = lambda: jax.lax.cond(index.overflowed, fallback, fused)
        else:
            dense = fused
    else:
        dense = lambda: dense_eval(cache=True)

    if active_capacity is None:
        force = dense()
        return jnp.where(out_mask[:, None], force, 0.0)

    # ---- §5.5 static-agent omission via work compaction -------------------
    a = int(active_capacity)
    active = pool.alive & ~pool.static
    n_active = jnp.sum(active.astype(jnp.int32))

    def compacted_path(_):
        # Deterministic sort-free compaction: active ids in index order
        # (rank = prefix sum + bounded scatter; no stable argsort).  The
        # candidate rows come from the NeighborContext's subset builder —
        # (A, 27M) for the active set only; the dense (C, 27M) tensor never
        # exists in this branch.
        act_ids, act_valid, _ = compact_indices(active, a)
        cand, mask = neighbors.candidates_for(act_ids, act_valid)
        gather = lambda x: jnp.take(x, act_ids, axis=0)
        sub_force = forces_from_candidates(
            gather(pool.position),
            gather(radius),
            cand,
            mask & act_valid[:, None],
            params,
            all_position=src_pos,
            all_radius=src_rad,
        )
        return (
            jnp.zeros((c, 3), sub_force.dtype)
            .at[act_ids]
            .add(jnp.where(act_valid[:, None], sub_force, 0.0))
        )

    # lax.cond: only one branch executes — overflow falls back to the full
    # evaluation (correctness), the common case pays O(actives) only.
    force = jax.lax.cond(
        n_active <= a, compacted_path, lambda _: dense(), operand=None
    )
    return jnp.where(out_mask[:, None], force, 0.0)


def update_static_flags(
    pool: AgentPool,
    displacement: Array,
    cand: Array,
    cand_mask: Array,
    params: ForceParams,
) -> AgentPool:
    """§5.5 static detection: an agent may be skipped next iteration iff
    neither it nor any neighbor moved more than the tolerance this iteration.
    """
    moved = jnp.linalg.norm(displacement, axis=-1) > params.static_tolerance
    moved = moved & pool.alive
    safe = jnp.where(cand_mask, cand, 0)
    neighbor_moved = jnp.any(jnp.take(moved, safe) & cand_mask, axis=1)
    static = pool.alive & ~moved & ~neighbor_moved
    return pool.replace(static=static)


def update_static_flags_celllist(
    spec: GridSpec,
    index: GridIndex,
    pool: AgentPool,
    displacement: Array,
    params: ForceParams,
    query_position: Optional[Array] = None,
    ghost_alive: Optional[Array] = None,
) -> AgentPool:
    """§5.5 static detection through the cell list — no dense candidates.

    Equivalent to :func:`update_static_flags` on the same index:
    "any candidate moved" is lifted to "any agent in the 27-box moved", via a
    per-cell any-reduction over ``cell_list`` (O(n_cells·M)) and a (N, 27)
    cell-level gather — the candidate version's (N, 27·M) gather never
    exists.  The two differ only in whether *self* counts as a neighbor (an
    agent that moved is non-static either way), so the flags are identical
    for agents alive at index-build time.  Agents born mid-step read a real
    stencil here — at the slot's ``query_position``, i.e. its pre-birth
    stored value — where the candidate version's build-time mask blanks
    theirs entirely; that makes this version at least as conservative, but
    neither evaluates the newborn's true neighborhood (both rely on its
    birth displacement tripping the ``moved`` test, which a child spawned
    within tolerance of a dead slot's stale position would evade).

    ``query_position``: the positions the index was built from (defaults to
    the pool's current positions; the engine passes the step-start positions
    so the stencil matches the one behaviors and forces saw).

    ``ghost_alive``: alive flags for source rows *beyond* the pool — the
    distributed engine's aura agents (§6.2.1), whose cell-list slots hold
    ids ≥ ``pool.capacity``.  Their per-step displacement is not locally
    known (they are exchange-time snapshots), so any live ghost is
    conservatively treated as moved: an agent whose neighborhood reaches
    into the halo never goes static.  Without it (single-node), out-of-pool
    slots cannot exist and the source set is the pool itself.
    """
    moved = jnp.linalg.norm(displacement, axis=-1) > params.static_tolerance
    moved = moved & pool.alive

    c = pool.capacity
    src_moved = moved if ghost_alive is None else jnp.concatenate(
        [moved, ghost_alive]
    )
    slot_valid = index.cell_list < src_moved.shape[0]
    safe = jnp.where(slot_valid, index.cell_list, 0)
    cell_moved = jnp.any(jnp.take(src_moved, safe) & slot_valid, axis=1)  # (n_cells,)

    qpos = pool.position if query_position is None else query_position
    nbr_cid, in_range = neighbor_cell_ids(spec, qpos)                 # (N, 27)
    neighbor_moved = jnp.any(cell_moved[nbr_cid] & in_range, axis=1)

    static = pool.alive & ~moved & ~neighbor_moved
    return pool.replace(static=static)
