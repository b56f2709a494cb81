"""jit'd dispatch for the sort-free within-cell rank primitive (§5.3.1).

``cell_rank`` computes, per agent, its rank among same-cell agents of lower
index — the quantity the grid build scatters into ``cell_list[cell, rank]``.
The seed derivation was a stable ``argsort(cid)`` (O(C log C), the last sort
on the per-step hot path); both impls here are sort-free tiled-histogram
passes (per-tile per-cell counts → exclusive scan over tiles → intra-tile
ranks), the same cumsum-rank idiom as ``agents.compact_indices`` generalized
from a boolean mask to a multi-valued key:

  impl="xla"        pure-XLA scatter/cumsum/gather version — interpret-safe,
                    the container and test default (like force_impl's
                    "reference"); histogram lives in HBM, O(C·L + T·NC).
  impl="pallas"     the Pallas kernel (kernel.py): running histogram in
                    VMEM scratch, intra-tile ranks on the MXU; one read of
                    cid + one write of rank reach HBM.
  impl="reference"  O(C²) dense oracle (ref.py) — validation only.

Tile size defaults to ≈ √(n_cells): total work C·L + C·NC/L is minimized at
L* = √NC (pairwise intra-tile comparisons vs per-tile histogram traffic).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import kernel as _kernel
from .ref import cell_rank_ref

Array = jax.Array

IMPLS = ("xla", "pallas", "reference")


def _default_tile(c: int, n_cells: int) -> int:
    """L ≈ √(n_cells+1), power of two, clamped to [32, 1024] and to the
    smallest power of two covering the population (no pointless padding)."""
    l = 1
    while l * l < n_cells + 1:
        l <<= 1
    cap = 32
    while cap < c and cap < 1024:
        cap <<= 1
    return max(32, min(l, cap, 1024))


def _rank_xla(cid_tiles: Array, n_cells: int) -> Array:
    """Tiled-histogram ranks in pure XLA over ``(T, L)`` tiled cell ids."""
    t, l = cid_tiles.shape
    rows = jnp.arange(t, dtype=jnp.int32)[:, None]
    hist = jnp.zeros((t, n_cells + 1), jnp.int32).at[rows, cid_tiles].add(1)
    offs = jnp.cumsum(hist, axis=0) - hist               # exclusive over tiles
    tile_off = jnp.take_along_axis(offs, cid_tiles, axis=1)
    earlier = jnp.arange(l)[:, None] > jnp.arange(l)[None, :]
    same = cid_tiles[:, :, None] == cid_tiles[:, None, :]
    intra = jnp.sum((same & earlier[None]).astype(jnp.int32), axis=2)
    return tile_off + intra


@functools.partial(
    jax.jit, static_argnames=("n_cells", "impl", "tile", "interpret")
)
def cell_rank(
    cid: Array,
    *,
    n_cells: int,
    impl: str = "xla",
    tile: int | None = None,
    interpret: bool | None = None,
) -> Array:
    """``rank[i] = |{j < i : cid[j] == cid[i]}|`` — sort-free, (C,) int32.

    ``cid`` holds values in ``[0, n_cells]`` (``n_cells`` itself is the
    dead-agent sentinel; sentinel rows rank among themselves, harmless —
    the build masks them out).  ``tile`` overrides the ≈√NC tile length
    (tests pass small inputs a coarse tile so the interpret-mode Pallas
    grid stays a handful of programs).  ``interpret`` defaults to
    :func:`repro.kernels.interpret_default` (the backend decides).
    """
    if impl not in IMPLS:
        raise ValueError(f"unknown cell_rank impl {impl!r}; expected {IMPLS}")
    cid = cid.astype(jnp.int32)
    if impl == "reference":
        return cell_rank_ref(cid)
    c = cid.shape[0]
    ncp = -(-(n_cells + 1) // 128) * 128                 # lane-aligned width
    l = int(tile) if tile else _default_tile(c, n_cells)
    if impl == "pallas" and tile is None:
        # VMEM bound: each program holds ~(L, NCP) f32 + i32 one-hots plus
        # the (L, L) tri matrix — cap L so the default fits a conservative
        # VMEM budget on real hardware (interpret mode has no such limit,
        # but the default must compile under Mosaic too).
        budget = 8 * 1024 * 1024
        cap = max(8, budget // (9 * ncp))                # ≈8 B per one-hot col
        while cap & (cap - 1):
            cap &= cap - 1                               # floor to pow2
        l = min(l, cap)
    t = -(-c // l)
    pad = t * l - c
    if pad:
        cid = jnp.concatenate([cid, jnp.full((pad,), n_cells, jnp.int32)])
    if impl == "xla":
        rank = _rank_xla(cid.reshape(t, l), n_cells)
        return rank.reshape(-1)[:c]
    out = _kernel.cell_rank_tiled(
        cid.reshape(t, l).T, hist_width=ncp, interpret=interpret
    )
    return out.T.reshape(-1)[:c]
