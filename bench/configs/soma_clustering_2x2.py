"""Soma clustering distributed over a 2x2 mesh (TeraAgent weak scaling,
ch 6): the one-chip model, cut along x and y, with the halo exchange of one
cell and migration every step.  The domain follows ``chip_smoke.py``'s
(buffers hold twice the expected agents of a face slab)."""

from __future__ import annotations

from repro.core.distributed import DomainConfig

from configs.soma_clustering import build, exposure_op, kinds  # noqa: F401  (same model)


def domain(cfg: dict) -> DomainConfig:
    lo, hi = cfg["space"]
    space = hi - lo
    sx, sy = cfg["mesh"]
    extent = space / sx
    slab = extent * space * cfg["density_per_unit3"]
    cell = cfg["cell_size"]
    return DomainConfig(
        mesh_axes=tuple(cfg["mesh_axes"]), axis_sizes=(sx, sy), extent=extent,
        halo_width=cell, depth=space, halo_codec=cfg["halo_codec"],
        halo_capacity=int(2 * cell * slab) + 64,
        migrate_capacity=int(2 * 2.0 * slab) + 64,
    )


def capacity(cfg: dict) -> int:
    per_chip = cfg["agents"] // (cfg["mesh"][0] * cfg["mesh"][1])
    return per_chip + int(per_chip * cfg["per_chip_headroom"]) + 64
