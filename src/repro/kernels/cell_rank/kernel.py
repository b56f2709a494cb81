"""Pallas TPU kernel: tiled-histogram within-cell ranking (§5.3.1 build).

The grid build needs, per agent, its *rank within its cell* — the count of
lower-indexed agents sharing its cell id — to scatter agent ids into the
dense ``(n_cells, M)`` cell list.  The seed engine derived ranks from a
stable ``argsort(cid)``, the last O(C log C) sort on the per-step hot path
(ROADMAP; BioDynaMo's §5.3.1 build is O(#agents) by construction, and
arXiv:2301.06984 shows the build dominating step time once forces are
optimized).  This kernel computes the same ranks sort-free:

  * agents are split into **tiles** of L consecutive indices; the grid is
    one program per tile, executed in index order (the default sequential
    TPU grid — no ``parallel`` dimension semantics, which would break the
    running histogram below);
  * a VMEM scratch row holds the **running per-cell histogram** of all
    earlier tiles; ``rank = hist[cid] + intra_tile_rank``;
  * the intra-tile rank is a strict-lower-triangular matmul against the
    tile's one-hot cell matrix (MXU work, exact in f32 for L ≤ 2²⁴);
    the cross-tile offset and the histogram update are one-hot reductions
    (i32 — exact at any population);
  * no gather, no scatter, no sort: every step is an iota comparison, a
    matmul, or an axis reduction, so the kernel lowers on Mosaic and in
    interpret mode identically.

Cost per tile is O(L·NC + L²) for NC = padded cell count; the wrapper in
ops.py picks L ≈ √NC so total work is O(C·√NC) — and, unlike the argsort,
it streams: HBM traffic is one read of ``cid`` plus one write of ``rank``
(the (L, NC) one-hot never leaves VMEM).  VMEM per program is O(L·NC)
bytes; callers with huge cell counts should lower L (or use the pure-XLA
fallback, whose histogram lives in HBM).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_default

Array = jax.Array


def _rank_kernel(cid_ref, out_ref, hist_ref):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    cid = cid_ref[...]                                   # (L, 1) i32
    l = cid.shape[0]
    ncp = hist_ref.shape[1]

    cols = jax.lax.broadcasted_iota(jnp.int32, (l, ncp), 1)
    oh = cid == cols                                     # (L, NC) one-hot
    oh_f = oh.astype(jnp.float32)
    oh_i = oh.astype(jnp.int32)

    # intra-tile rank: E[i, c] = # earlier rows of THIS tile in cell c —
    # a strict-lower-triangular matmul; row-pick via the one-hot itself.
    tri = (
        jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
        > jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    ).astype(jnp.float32)
    earlier = jax.lax.dot(tri, oh_f, preferred_element_type=jnp.float32)
    intra = jnp.sum(oh_f * earlier, axis=1, keepdims=True)     # (L, 1) ≤ L

    # cross-tile offset: agents of the same cell in ALL earlier tiles.
    tile_off = jnp.sum(oh_i * hist_ref[...], axis=1, keepdims=True)

    out_ref[...] = intra.astype(jnp.int32) + tile_off
    hist_ref[...] += jnp.sum(oh_i, axis=0, keepdims=True)


def cell_rank_tiled(
    cid_cols: Array, hist_width: int, interpret: bool | None = None
) -> Array:
    """Within-cell ranks for tile-column-major cell ids.

    ``cid_cols`` is ``(L, T)`` int32 — column t holds agents
    ``[t·L, (t+1)·L)`` (the ops.py wrapper reshapes/pads the flat id
    vector).  ``hist_width`` is the padded cell-id range (> max cell id;
    lane-aligned by the wrapper).  Returns ``(L, T)`` int32 ranks.
    """
    l, t = cid_cols.shape
    return pl.pallas_call(
        _rank_kernel,
        grid=(t,),
        in_specs=[pl.BlockSpec((l, 1), lambda i: (0, i))],
        out_specs=pl.BlockSpec((l, 1), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((l, t), jnp.int32),
        scratch_shapes=[pltpu.VMEM((1, hist_width), jnp.int32)],
        interpret=interpret_default(interpret),
        name="cell_rank_tiled",
    )(cid_cols)
