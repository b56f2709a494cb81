"""jit'd public wrapper for the diffusion stencil kernel."""

from __future__ import annotations

import functools

import jax

from .kernel import diffusion_step_pallas
from .ref import diffusion_step_ref

Array = jax.Array


@functools.partial(
    jax.jit, static_argnames=("nu_dt_dx2", "decay_dt", "impl", "interpret")
)
def diffusion_step(
    u: Array,
    nu_dt_dx2: float,
    decay_dt: float = 0.0,
    impl: str = "pallas",
    interpret: bool | None = None,
) -> Array:
    """One Eq-4.3 step.  impl: "pallas" | "reference"."""
    if impl == "reference":
        return diffusion_step_ref(u, nu_dt_dx2, decay_dt)
    return diffusion_step_pallas(
        u, nu_dt_dx2=nu_dt_dx2, decay_dt=decay_dt, interpret=interpret
    )
