"""Every agent of the component drawn independently and uniformly over the
space less the configuration's margin."""

import jax
import jax.numpy as jnp


def draw(key, n: int, lo: float, hi: float, params: dict):
    return jax.random.uniform(key, (n, 3), jnp.float32, lo, hi)
