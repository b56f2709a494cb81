"""Pallas TPU kernel: fused RMSNorm.

Every pre-norm block reads the residual stream twice (stats + scale) when
unfused; this kernel keeps a (TILE_ROWS, D) tile VMEM-resident, computes the
fp32 row statistics, and writes the normalized tile once — one HBM read and
one write per element, the norm's bandwidth roofline.  Rows are the flattened
(batch·seq) dim; D is the lane dim (d_model, 128-aligned for the VPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_default

Array = jax.Array

TILE_ROWS = 256


def _rmsnorm_kernel(x_ref, scale_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)               # (R, D)
    ms = jnp.mean(jnp.square(x), axis=1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps) * scale_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "interpret", "tile_rows"))
def rmsnorm_rows(
    x: Array,          # (N, D)
    scale: Array,      # (D,)
    eps: float = 1e-6,
    interpret: bool | None = None,
    tile_rows: int = TILE_ROWS,
) -> Array:
    n, d = x.shape
    pad = (-n) % tile_rows
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=((n + pad) // tile_rows,),
        in_specs=[
            pl.BlockSpec((tile_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((tile_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n + pad, d), x.dtype),
        interpret=interpret_default(interpret),
    )(xp, scale)
    return out[:n]
