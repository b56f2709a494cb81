"""Pallas TPU kernel: pairwise contact forces (Eq 4.1).

The paper's dominant operation (§5.6.3) is the O(N·K) force loop over each
agent's candidate neighbors.  TPU mapping:

  * the candidate *gather* (irregular) stays in XLA, which handles dynamic
    gathers well; the kernel fuses the dense O(N·K) force arithmetic — the
    FLOP hot spot — into a single VMEM-resident pass (one read of each
    candidate block, one accumulation per agent tile, no HBM intermediates
    for dist/δ/r̄/magnitude, which a naive jnp chain would materialize).
  * layout is component-planar: positions enter as (3, N) / (3, N, K) so the
    lane dimension is the K candidates (128-aligned) and the VPU sees clean
    (TILE_N, TILE_K) tiles — this is the §5.4.2 "SoA + sorted" memory-layout
    insight carried down to the register level.
  * grid = (N / TILE_N, K / TILE_K); the K dimension accumulates in the
    output block (revisited across the inner grid axis), so arbitrary K fits
    in a fixed VMEM budget.

Validated in interpret mode against ref.py; on TPU hardware the same code
lowers through Mosaic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_default

Array = jax.Array

TILE_N = 128
TILE_K = 128


def _force_kernel(
    pos_ref,        # (3, TILE_N)      query positions (component-planar)
    rad_ref,        # (1, TILE_N)
    cpos_ref,       # (3, TILE_N, TILE_K)
    crad_ref,       # (1, TILE_N, TILE_K)
    cmask_ref,      # (1, TILE_N, TILE_K)  int8 mask
    out_ref,        # (3, TILE_N)      accumulated force
    *,
    k: float,
    gamma: float,
    n_k_blocks: int,
):
    kj = pl.program_id(1)

    @pl.when(kj == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    px = pos_ref[0, :][:, None]              # (TILE_N, 1)
    py = pos_ref[1, :][:, None]
    pz = pos_ref[2, :][:, None]
    r = rad_ref[0, :][:, None]

    cx = cpos_ref[0, :, :]                   # (TILE_N, TILE_K)
    cy = cpos_ref[1, :, :]
    cz = cpos_ref[2, :, :]
    cr = crad_ref[0, :, :]
    m = cmask_ref[0, :, :] != 0

    dx = px - cx
    dy = py - cy
    dz = pz - cz
    dist = jnp.sqrt(dx * dx + dy * dy + dz * dz + 1e-20)
    delta = r + cr - dist
    overlap = (delta > 0.0) & m
    rbar = r * cr / jnp.maximum(r + cr, 1e-20)
    mag = k * delta - gamma * jnp.sqrt(jnp.maximum(rbar * delta, 0.0))
    scale = jnp.where(overlap, mag / dist, 0.0)          # (TILE_N, TILE_K)

    fx = jnp.sum(scale * dx, axis=1)                     # (TILE_N,)
    fy = jnp.sum(scale * dy, axis=1)
    fz = jnp.sum(scale * dz, axis=1)
    out_ref[...] += jnp.stack([fx, fy, fz], axis=0)


@functools.partial(
    jax.jit, static_argnames=("k", "gamma", "interpret", "tile_n", "tile_k")
)
def pairwise_force_planar(
    pos: Array,        # (3, N) f32
    rad: Array,        # (1, N) f32
    cand_pos: Array,   # (3, N, K) f32
    cand_rad: Array,   # (1, N, K) f32
    cand_mask: Array,  # (1, N, K) int8
    k: float = 2.0,
    gamma: float = 1.0,
    interpret: bool | None = None,
    tile_n: int = TILE_N,
    tile_k: int = TILE_K,
) -> Array:
    """Component-planar entry point; shapes must be tile-aligned."""
    _, n = pos.shape
    kdim = cand_pos.shape[-1]
    assert n % tile_n == 0 and kdim % tile_k == 0, (n, kdim)
    n_k_blocks = kdim // tile_k

    grid = (n // tile_n, n_k_blocks)
    kernel = functools.partial(
        _force_kernel, k=k, gamma=gamma, n_k_blocks=n_k_blocks
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((3, tile_n), lambda i, j: (0, i)),
            pl.BlockSpec((1, tile_n), lambda i, j: (0, i)),
            pl.BlockSpec((3, tile_n, tile_k), lambda i, j: (0, i, j)),
            pl.BlockSpec((1, tile_n, tile_k), lambda i, j: (0, i, j)),
            pl.BlockSpec((1, tile_n, tile_k), lambda i, j: (0, i, j)),
        ],
        out_specs=pl.BlockSpec((3, tile_n), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((3, n), jnp.float32),
        interpret=interpret_default(interpret),
        name="pairwise_force_planar",
    )(pos, rad, cand_pos, cand_rad, cand_mask)
