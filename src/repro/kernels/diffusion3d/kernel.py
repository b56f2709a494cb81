"""Pallas TPU kernel: 7-point diffusion stencil (Eq 4.3).

TPU stencil strategy: Pallas blocks are non-overlapping, so the ±1 halo a
stencil needs cannot come from the BlockSpec index_map.  Instead the wrapper
materializes the zero-padded array once and passes six *shifted views* (XLA
slices — fused, no copies on TPU) plus the center; the kernel is then a pure
VPU elementwise combine over aligned (TILE_X, ny, nz) blocks:

    u⁺ = u·(1 − μΔt) + c·(xm + xp + ym + yp + zm + zp − 6u)

This trades 7× nominal reads for perfect alignment; XLA's fusion keeps the
actual HBM traffic at 2 arrays (in+out), which is the stencil's roofline.
The grid is 1-D over x-slabs so ny·nz·TILE_X·4B stays within VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_default

Array = jax.Array


def _stencil_kernel(u_ref, xm_ref, xp_ref, ym_ref, yp_ref, zm_ref, zp_ref, o_ref,
                    *, nu_dt_dx2: float, decay_dt: float):
    u = u_ref[...]
    lap = (
        xm_ref[...] + xp_ref[...] + ym_ref[...] + yp_ref[...]
        + zm_ref[...] + zp_ref[...] - 6.0 * u
    )
    o_ref[...] = u * (1.0 - decay_dt) + nu_dt_dx2 * lap


@functools.partial(
    jax.jit, static_argnames=("nu_dt_dx2", "decay_dt", "interpret", "tile_x")
)
def diffusion_step_pallas(
    u: Array, nu_dt_dx2: float, decay_dt: float,
    interpret: bool | None = None, tile_x: int = 8,
) -> Array:
    nx, ny, nz = u.shape
    z = jnp.pad(u, 1)
    c = z[1:-1, 1:-1, 1:-1]
    xm = z[:-2, 1:-1, 1:-1]
    xp = z[2:, 1:-1, 1:-1]
    ym = z[1:-1, :-2, 1:-1]
    yp = z[1:-1, 2:, 1:-1]
    zm = z[1:-1, 1:-1, :-2]
    zp = z[1:-1, 1:-1, 2:]

    pad_x = (-nx) % tile_x
    args = [c, xm, xp, ym, yp, zm, zp]
    if pad_x:
        args = [jnp.pad(a, ((0, pad_x), (0, 0), (0, 0))) for a in args]
    nxp = nx + pad_x

    spec = pl.BlockSpec((tile_x, ny, nz), lambda i: (i, 0, 0))
    out = pl.pallas_call(
        functools.partial(_stencil_kernel, nu_dt_dx2=nu_dt_dx2, decay_dt=decay_dt),
        grid=(nxp // tile_x,),
        in_specs=[spec] * 7,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((nxp, ny, nz), u.dtype),
        interpret=interpret_default(interpret),
        name="diffusion_step_pallas",
    )(*args)
    return out[:nx]
