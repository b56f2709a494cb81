"""Extracellular diffusion (§4.5.2, Eq 4.3).

Fick's second law with decay, discretized by the central difference scheme:

    u⁺ = u·(1 − μΔt) + νΔt/Δx² · (u[i±1] − 2u) + … (y, z terms)

Boundary behaviour matches BioDynaMo's default: substances diffuse *out* of
the simulation space (outside concentration ≡ 0).  Agents couple to the grid
through ``increase_concentration`` (secretion) and ``gradient_at`` /
``concentration_at`` (chemotaxis), exactly the three primitives the paper's
soma-clustering model uses (Algorithms 6–7).

The stencil core is the `repro.kernels.diffusion3d` Pallas kernel on TPU;
the pure-jnp path below is the oracle and the CPU/dry-run implementation.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

Array = jax.Array

DIFFUSION_IMPLS = ("reference", "pallas")


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DiffusionGrid:
    """One extracellular substance on a regular grid over the sim space.

    ``n_valid`` / ``frame_shift`` support *ghost-voxel padding* (uneven
    distributed substance splits, DESIGN.md §4): when a global resolution
    does not divide the device mesh evenly, every device carries a uniform
    ``ceil(R/S)``-voxel frame whose tail voxels beyond ``n_valid[d]`` are
    padding — outside the simulated domain, pinned to zero by diffusion and
    clipped out of sampling/secretion.  ``frame_shift[d]`` is the local
    coordinate of the frame's low voxel corner (the global voxel lattice is
    generally misaligned with the device frame when the split is uneven).
    Both stay ``None`` single-node and for even splits — the grid then
    behaves exactly as before."""

    concentration: Array  # (nx, ny, nz) float32
    # static metadata
    origin: Tuple[float, float, float] = dataclasses.field(metadata=dict(static=True))
    spacing: float = dataclasses.field(metadata=dict(static=True))
    diffusion_coefficient: float = dataclasses.field(metadata=dict(static=True))
    decay_constant: float = dataclasses.field(metadata=dict(static=True))
    # ghost-voxel padding metadata (per-device data, not static: the valid
    # extent differs across devices in one SPMD program)
    n_valid: Array | None = None       # (3,) i32 valid voxels per dim
    frame_shift: Array | None = None   # (3,) f32 lattice offset of voxel 0

    @property
    def resolution(self) -> Tuple[int, int, int]:
        return self.concentration.shape  # type: ignore[return-value]


def make_grid(
    min_bound: float,
    max_bound: float,
    resolution: int,
    diffusion_coefficient: float,
    decay_constant: float = 0.0,
) -> DiffusionGrid:
    spacing = (max_bound - min_bound) / resolution
    conc = jnp.zeros((resolution, resolution, resolution), jnp.float32)
    return DiffusionGrid(
        concentration=conc,
        origin=(min_bound, min_bound, min_bound),
        spacing=spacing,
        diffusion_coefficient=diffusion_coefficient,
        decay_constant=decay_constant,
    )


def stability_limit(grid: DiffusionGrid) -> float:
    """Max Δt for explicit-scheme stability: Δt ≤ Δx²/(6ν)."""
    return grid.spacing**2 / (6.0 * max(grid.diffusion_coefficient, 1e-30))


def _laplacian_zero_outside(u: Array, dx: float) -> Array:
    """7-point Laplacian with zero concentration outside the boundary."""
    z = jnp.pad(u, 1)  # zero-pad all six faces
    lap = (
        z[2:, 1:-1, 1:-1]
        + z[:-2, 1:-1, 1:-1]
        + z[1:-1, 2:, 1:-1]
        + z[1:-1, :-2, 1:-1]
        + z[1:-1, 1:-1, 2:]
        + z[1:-1, 1:-1, :-2]
        - 6.0 * u
    )
    return lap / (dx * dx)


def diffuse(grid: DiffusionGrid, dt: float, impl: str = "reference") -> DiffusionGrid:
    """One explicit central-difference step of Eq 4.3 (``impl`` in
    :data:`DIFFUSION_IMPLS`)."""
    if impl not in DIFFUSION_IMPLS:
        raise ValueError(
            f"unknown diffusion impl {impl!r}; expected one of {DIFFUSION_IMPLS}"
        )
    if impl == "pallas":
        from repro.kernels.diffusion3d import ops as d3_ops

        new = d3_ops.diffusion_step(
            grid.concentration,
            nu_dt_dx2=grid.diffusion_coefficient * dt / grid.spacing**2,
            decay_dt=grid.decay_constant * dt,
        )
        return dataclasses.replace(grid, concentration=new)
    u = grid.concentration
    lap = _laplacian_zero_outside(u, grid.spacing)
    new = u * (1.0 - grid.decay_constant * dt) + grid.diffusion_coefficient * dt * lap
    return dataclasses.replace(grid, concentration=new)


# ---------------------------------------------------------------- coupling

def _grid_coords(grid: DiffusionGrid, position: Array) -> Array:
    origin = jnp.asarray(grid.origin, jnp.float32)
    rel = position - origin
    if grid.frame_shift is not None:
        rel = rel - grid.frame_shift
    rel = rel / grid.spacing - 0.5
    return rel  # fractional voxel coordinates (cell-centered)


def _effective_resolution(grid: DiffusionGrid) -> Array:
    """(3,) i32 — the sampled extent: the valid voxel count when the grid
    carries ghost-voxel padding, else the stored resolution.  Clipping to
    it keeps padded voxels out of sampling and secretion (a position beyond
    the last valid voxel clips onto it, matching the single-node edge
    clip)."""
    if grid.n_valid is not None:
        return jnp.asarray(grid.n_valid, jnp.int32)
    return jnp.asarray(grid.resolution, jnp.int32)


def _nearest_voxel(grid: DiffusionGrid, position: Array) -> Array:
    res = _effective_resolution(grid)
    ijk = jnp.round(_grid_coords(grid, position)).astype(jnp.int32)
    return jnp.clip(ijk, 0, res - 1)


def increase_concentration(
    grid: DiffusionGrid, position: Array, amount: Array, mask: Array | None = None
) -> DiffusionGrid:
    """Scatter-add secretion at agent positions (Algorithm 6)."""
    ijk = _nearest_voxel(grid, position)
    amount = jnp.broadcast_to(jnp.asarray(amount, jnp.float32), position.shape[:-1])
    if mask is not None:
        amount = jnp.where(mask, amount, 0.0)
    new = grid.concentration.at[ijk[..., 0], ijk[..., 1], ijk[..., 2]].add(amount)
    return dataclasses.replace(grid, concentration=new)


def concentration_at(grid: DiffusionGrid, position: Array) -> Array:
    ijk = _nearest_voxel(grid, position)
    return grid.concentration[ijk[..., 0], ijk[..., 1], ijk[..., 2]]


def gradient_at(grid: DiffusionGrid, position: Array, normalized: bool = True) -> Array:
    """Central-difference gradient sampled at agent positions (Algorithm 7)."""
    res = _effective_resolution(grid)
    ijk = _nearest_voxel(grid, position)

    def sample(off: Tuple[int, int, int]) -> Array:
        q = jnp.clip(ijk + jnp.asarray(off, jnp.int32), 0, res - 1)
        return grid.concentration[q[..., 0], q[..., 1], q[..., 2]]

    gx = (sample((1, 0, 0)) - sample((-1, 0, 0))) / (2.0 * grid.spacing)
    gy = (sample((0, 1, 0)) - sample((0, -1, 0))) / (2.0 * grid.spacing)
    gz = (sample((0, 0, 1)) - sample((0, 0, -1))) / (2.0 * grid.spacing)
    g = jnp.stack([gx, gy, gz], axis=-1)
    if normalized:
        norm = jnp.linalg.norm(g, axis=-1, keepdims=True)
        g = jnp.where(norm > 1e-12, g / jnp.maximum(norm, 1e-12), 0.0)
    return g


def analytical_point_source(
    q: float, d: float, r: Array, t: Array
) -> Array:
    """Instantaneous point source in free 3D space (Fig 4.9 convergence test):

        u(r, t) = Q / (4πDt)^{3/2} · exp(−r² / (4Dt))
    """
    denom = (4.0 * jnp.pi * d * t) ** 1.5
    return q / denom * jnp.exp(-(r * r) / (4.0 * d * t))
