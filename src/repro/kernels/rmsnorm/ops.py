"""jit'd public wrapper for the fused RMSNorm kernel."""

from __future__ import annotations

import functools

import jax

from .kernel import rmsnorm_rows
from .ref import rmsnorm_ref

Array = jax.Array


@functools.partial(jax.jit, static_argnames=("eps", "impl", "interpret"))
def rmsnorm(
    x: Array,          # (..., D)
    scale: Array,      # (D,)
    eps: float = 1e-6,
    impl: str = "pallas",
    interpret: bool | None = None,
) -> Array:
    if impl == "reference":
        return rmsnorm_ref(x, scale, eps)
    shape = x.shape
    y = rmsnorm_rows(x.reshape(-1, shape[-1]), scale, eps=eps, interpret=interpret)
    return y.reshape(shape)
