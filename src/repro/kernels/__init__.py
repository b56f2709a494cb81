"""Pallas TPU kernels for the perf-critical compute layers.

Each kernel package has:
  kernel.py — pl.pallas_call + BlockSpec tiling (Mosaic on TPU; the Pallas
              interpreter on CPU — see :func:`interpret_default`)
  ops.py    — jit'd dispatch wrapper (impl="pallas" | "reference" | …)
  ref.py    — pure-jnp oracle

Kernels:
  pairwise_force  — Eq 4.1 contact forces over dense candidates, §5.6.3
  cell_force      — Eq 4.1 forces fused with the cell-list walk (no dense
                    candidate tensor; DESIGN.md §4)
  cell_rank       — sort-free within-cell ranking for the grid build
                    (tiled histogram; kills the per-step argsort, §5.3.1)
  diffusion3d     — Eq 4.3 seven-point stencil
  flash_attention — online-softmax attention for the LM stack (GQA/causal/window)
  rmsnorm         — fused residual-stream normalization (one read, one write)
"""

from __future__ import annotations

import jax


def interpret_default(interpret: bool | None = None) -> bool:
    """Pallas interpret mode for one ``pallas_call``.

    An explicit ``interpret`` wins (compile-only tests pass ``False`` to
    lower for a described TPU from a CPU process).  ``None`` — every
    kernel's default — means "interpret iff JAX's default backend is the
    CPU", read when the kernel is traced, so on a TPU no kernel runs through
    the interpreter and on the CPU no kernel asks for Mosaic.
    """
    if interpret is not None:
        return interpret
    return jax.default_backend() == "cpu"
