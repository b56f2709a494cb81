"""jit'd public wrapper for the fused cell-list force kernel.

``cell_list_force`` consumes the grid's ``cell_list`` *directly*: the only
XLA-side work is the O(n_cells·M) gather into the cell-major planar layout
and an O(N) gather of per-slot forces back to agent order, through the
grid build's own inverse map ``slot_of_agent``.  The ``(N, 27·M)``
candidate tensor, its boolean mask, and the ``(N, K, 3)`` candidate-position
gather of the dense path never exist.

Semantics match the candidate path exactly when no cell overflowed: the pair
set is "all agents in the 27-box neighborhood, minus self".  Agents dropped
from an overflowing cell are invisible to the cell list — they exert no
force *and receive none* here (the dense path still computes one-sided
forces for them).  `repro.core.forces.mechanical_forces` guards this with a
``lax.cond`` fallback on ``index.overflowed`` (correctness first, like the
§5.5 compaction fallback).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import spans

from . import kernel as _kernel
from .ref import cell_list_force_ref

Array = jax.Array


def _cell_major_planar(
    position: Array, radius: Array, cell_list: Array, dims: tuple
):
    """Gather pool arrays into padded cell-major planar layout.

    Returns ``(cpos, crad, cval)`` shaped ``(·, n_cols + 2·pad, nz, M)`` with
    ``pad = ny + 1`` ghost columns per side (empty: cval = 0).
    """
    nx, ny, nz = dims
    n_cells, m = cell_list.shape
    c = position.shape[0]
    valid = cell_list < c                                  # sentinel C = empty
    safe = jnp.where(valid, cell_list, 0)
    cpos = jnp.take(position, safe, axis=0)                # (n_cells, M, 3)
    crad = jnp.where(valid, jnp.take(radius, safe, axis=0), 0.0)

    n_cols = nx * ny
    pad = ny + 1
    padw = [(0, 0), (pad, pad), (0, 0), (0, 0)]
    cpos = jnp.pad(
        jnp.moveaxis(cpos, -1, 0).reshape(3, n_cols, nz, m), padw
    )
    crad = jnp.pad(crad.reshape(1, n_cols, nz, m), padw)
    cval = jnp.pad(valid.astype(jnp.int8).reshape(1, n_cols, nz, m), padw)
    return cpos, crad, cval


@functools.partial(
    jax.jit, static_argnames=("dims", "k", "gamma", "impl", "interpret", "num_out")
)
def cell_list_force(
    position: Array,    # (S, 3) f32 — all indexed agents (pool, or pool+ghosts)
    radius: Array,      # (S,) f32
    cell_list: Array,   # (n_cells, M) int32, empty slots = S
    slot_of_agent: Array,  # (S,) int32 flat slot per row, n_cells·M = none
    dims: tuple,        # (nx, ny, nz) static — n_cells must equal nx·ny·nz
    k: float = 2.0,
    gamma: float = 1.0,
    impl: str = "pallas",
    interpret: bool | None = None,
    num_out: int | None = None,
) -> Array:
    """Net Eq-4.1 force per agent, (num_out, 3), straight from the cell list.

    ``slot_of_agent`` is :attr:`repro.core.grid.GridIndex.slot_of_agent`
    of the same build: each row's flat slot in ``cell_list``, so the result
    is one gather of the kernel's per-slot forces.  A row holds at most one
    slot, so this equals scatter-adding every slot back (0 + x == x).

    ``num_out`` (default: all S rows) restricts the result to the first
    ``num_out`` source rows — the distributed engine passes its local pool
    capacity so forces land on local agents only while ghost (halo) slots'
    contributions are computed in-kernel but never gathered (§6.2.1:
    ghosts are read-only copies; their owners integrate them remotely).
    """
    nx, ny, nz = dims
    n_cells, m = cell_list.shape
    assert n_cells == nx * ny * nz, (cell_list.shape, dims)
    c = position.shape[0]
    out_n = c if num_out is None else int(num_out)

    if impl == "reference":
        return cell_list_force_ref(
            position, radius, cell_list, dims, k=k, gamma=gamma,
            num_out=num_out,
        )

    # Each stage runs under a named scope of its own (`repro.spans`), so a
    # device trace splits the force pass into its three stages.
    with jax.named_scope(spans.CELL_GATHER):
        cpos, crad, cval = _cell_major_planar(
            position, radius, cell_list, dims
        )
    with jax.named_scope(spans.CELL_KERNEL):
        slot_force = _kernel.cell_list_force_planar(
            cpos, crad, cval, dims, k=k, gamma=gamma, interpret=interpret
        )                                                   # (3, n_cols, nz, M)

    # Gather each row's force from its own slot, along the planes' minor
    # axis.  Dead and dropped rows hold the sentinel n_cells·M, out of range,
    # and read 0; ghost rows ≥ num_out are never gathered.
    with jax.named_scope(spans.CELL_SCATTER):
        return jnp.take(
            slot_force.reshape(3, n_cells * m), slot_of_agent[:out_n], axis=1,
            mode="fill", fill_value=0.0,
        ).T                                                 # (out_n, 3)


def window_defaults(c: int, block: int | None, window: int | None
                    ) -> tuple[int, int]:
    """Resolve the Morton window geometry ``(block, half_window)`` for a
    pool of ``c`` rows.

    block:  tile/window width; clipped to a power of two ≤ c's padded size
            so small test pools still tile.
    window: half-window in blocks; default covers ±1/8 of the pool — ample
            for a sorted pool at realistic densities (the dispatcher
            verifies per step) while keeping the sweep 2·H+1 ≪ C/B.
    """
    b = 128 if block is None else int(block)
    while b > 1 and b > c:
        b //= 2
    nbw = -(-c // b)
    h = max(1, -(-nbw // 8)) if window is None else int(window)
    return b, h


@functools.partial(
    jax.jit,
    static_argnames=("dims", "k", "gamma", "block", "window", "interpret"),
)
def cell_window_force(
    position: Array,       # (C, 3) f32 layout-sorted pool positions
    radius: Array,         # (C,) f32
    cell_of_agent: Array,  # (C,) int32 linear cell id (dead → n_cells)
    dims: tuple,           # (nx, ny, nz) static grid dims
    k: float = 2.0,
    gamma: float = 1.0,
    block: int | None = None,
    window: int | None = None,
    interpret: bool | None = None,
) -> Array:
    """Net Eq-4.1 force per agent, (C, 3), via the Morton-window kernel.

    The ``tile_order="morton"`` entry: no cell-major gather, no cell list —
    the kernel reads the pool arrays in storage order (contiguous DMA per
    tile) and masks pairs by 27-box adjacency of their cell ids.  Exact iff
    every agent's neighborhood lies within ``± window`` blocks of its own
    tile (guaranteed by the dispatcher's coverage check, or by
    ``window ≥ ceil(C/block)`` which degenerates to masked all-pairs).

    Summation order differs from the cell-list kernels (window-major vs
    cell-slot-major), so parity with them is to float tolerance, like every
    impl pair in this package.
    """
    c = position.shape[0]
    bw, h = window_defaults(c, block, window)
    cp = -(-c // bw) * bw
    pad = cp - c

    ppos = jnp.concatenate([position.T, radius[None]], axis=0)  # (4, C)
    n_cells = dims[0] * dims[1] * dims[2]
    pcid = cell_of_agent.astype(jnp.int32)
    if pad:
        ppos = jnp.pad(ppos, [(0, 0), (0, pad)])
        pcid = jnp.pad(pcid, [(0, pad)], constant_values=n_cells)

    with jax.named_scope(spans.CELL_KERNEL):
        out = _kernel.cell_window_force_planar(
            ppos, pcid[None], dims, k=k, gamma=gamma,
            block=bw, half_window=h, interpret=interpret,
        )
    return out[:3, :c].T
