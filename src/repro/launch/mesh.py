"""Production mesh construction.

A function (not a module-level constant) so importing never touches jax
device state.  Single pod: 16×16 = 256 chips (v5e pod slice); multi-pod:
2 pods × 256 = 512 chips with a leading "pod" axis whose collectives cross
the inter-pod links (DCI) — the dry-run proving the pod axis shards is the
multi-pod deliverable.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Mesh of ``shape`` over ``jax.devices()`` with Auto-sharded axes."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(shape),
    )
