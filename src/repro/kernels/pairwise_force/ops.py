"""jit'd public wrapper for the pairwise-force kernel.

Dispatches between the Pallas kernel (``impl="pallas"``; interpret-mode on
CPU, Mosaic on TPU) and the pure-jnp oracle (``impl="reference"``).  Handles
the candidate gather, component-planar layout change, and tile padding so
callers work with natural (N, 3)/(N, K) shapes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import kernel as _kernel
from .ref import pairwise_force_ref

Array = jax.Array


def _pad_to(x: Array, axis: int, multiple: int) -> Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("k", "gamma", "impl", "interpret"))
def pairwise_force(
    position: Array,   # (N, 3) f32 query agents
    radius: Array,     # (N,) f32
    cand: Array,       # (N, K) int32 indices into the source arrays
    cand_mask: Array,  # (N, K) bool
    k: float = 2.0,
    gamma: float = 1.0,
    impl: str = "pallas",
    interpret: bool | None = None,
    all_position: Array | None = None,  # (S, 3) candidate sources (default: queries)
    all_radius: Array | None = None,    # (S,)
) -> Array:
    """Net Eq-4.1 force per agent, (N, 3).

    ``all_position``/``all_radius``: the arrays candidate ids index into when
    they are a superset of the queries — the distributed engine's
    ghost-extended (local + halo) arrays (§6.2.1).  Defaults to the query
    arrays (single-node: sources == queries).
    """
    n, kdim = cand.shape
    src_pos = position if all_position is None else all_position
    src_rad = radius if all_radius is None else all_radius
    safe = jnp.where(cand_mask, cand, 0)
    cand_pos = jnp.take(src_pos, safe, axis=0)     # (N, K, 3)
    cand_rad = jnp.take(src_rad, safe, axis=0)     # (N, K)

    if impl == "reference":
        return pairwise_force_ref(
            position, radius, cand_pos, cand_rad, cand_mask, k=k, gamma=gamma
        )

    tile_n, tile_k = _kernel.TILE_N, _kernel.TILE_K
    # planar layout + tile padding
    pos_p = _pad_to(position.T.astype(jnp.float32), 1, tile_n)            # (3, N')
    rad_p = _pad_to(radius[None, :].astype(jnp.float32), 1, tile_n)       # (1, N')
    cpos_p = _pad_to(
        _pad_to(jnp.moveaxis(cand_pos, -1, 0).astype(jnp.float32), 1, tile_n), 2, tile_k
    )                                                                     # (3, N', K')
    crad_p = _pad_to(_pad_to(cand_rad[None].astype(jnp.float32), 1, tile_n), 2, tile_k)
    cmask_p = _pad_to(
        _pad_to(cand_mask[None].astype(jnp.int8), 1, tile_n), 2, tile_k
    )

    out = _kernel.pairwise_force_planar(
        pos_p, rad_p, cpos_p, crad_p, cmask_p,
        k=k, gamma=gamma, interpret=interpret,
    )
    return out[:, :n].T  # (N, 3)
