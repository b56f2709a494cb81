"""Correctness of a soma-clustering chunk against the plain reference.

The reference steps the program's state from the start of the compared
chunk through the same iterations: the layout sort (on sort steps),
secretion, chemotaxis, Eq 4.1 contact forces over every overlapping pair,
the closed boundary, diffusion, ageing and the exposure op.  Agents are
matched by tag.

The exposure dose of the chunk's last step is the reference's own
end-of-step concentration of each agent's own substance, sampled at the
program's end-of-step position of that agent.  The positions are held to
``position_gap`` on their own: a position one ulp off the reference's, as
force sums added in another order give, may lie across a voxel face, and
nearest-voxel sampling at the reference's position would then read the
neighbouring voxel's whole dose.  In a chunk of several steps only the last
step's dose is sampled so: the program's positions inside the chunk are not
at hand, and the earlier doses stand as the reference took them.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

import reference as ref
from checks import common

# Each limit lies between the program's largest reading over 33 seeds and
# the control's smallest over 3 (the same reference in bfloat16), on the
# chip at the cell's size (PERF.md gives the readings): position_gap 6.1e-4
# vs 12.6, substance_gap 3.8e-7 vs 0.71, exposure_gap (dosed at the
# program's positions) 2.4e-7 vs 0.43.  The layout is compared exactly.
LIMITS = {
    "layout_mismatch": 0,
    "position_gap": 1e-2,
    "substance_gap": 1e-3,
    "exposure_gap": 1e-3,
}

# The attribute under which ``step`` keeps each agent's exposure before the
# step's dose, for ``compare`` to dose it again at the program's positions.
PRE_DOSE = "exposure_before_dose"


def dosed(cfg: dict, exposure, grids: dict, pos, kind, alive, dtype):
    """``exposure`` plus one step's dose: the concentration of each agent's
    own substance at the voxel nearest ``pos``, times ``dt``."""
    lo, hi = cfg["space"]
    own = jnp.zeros(pos.shape[0], dtype)
    for sub in cfg["substances"]:
        res = sub["resolution"]
        c = ref.sample(grids[sub["name"]], pos, lo, (hi - lo) / res, res)
        own = jnp.where(kind == sub["kind"], c, own)
    return exposure + jnp.where(alive, own * cfg["dt"], 0)


def step(cfg: dict, s: dict, dtype) -> dict:
    """One iteration of the model from state ``s`` (arrays in slot order)."""
    lo, hi = cfg["space"]
    n, box = ref.grid_dims(lo, hi, cfg["cell_size"])
    dt = cfg["dt"]
    s = common.sort_if_due(s, cfg, lo, box, n)
    pos = s["position"].astype(dtype)
    alive, kind = s["alive"], s["kind"]
    build_ijk = ref.cell_coords(pos, lo, box, n)
    grids = {k: v.astype(dtype) for k, v in s["grids"].items()}
    geo = {}
    for sub in cfg["substances"]:
        res = sub["resolution"]
        geo[sub["name"]] = (lo, (hi - lo) / res, res)
        mask = alive & (kind == sub["kind"])
        grids[sub["name"]] = ref.secrete(grids[sub["name"]], pos, mask,
                                         sub["secretion"], *geo[sub["name"]])
    for sub in cfg["substances"]:
        g = ref.unit_gradient(grids[sub["name"]], pos, *geo[sub["name"]])
        mask = alive & (kind == sub["kind"])
        pos = pos + jnp.where(mask[:, None], g * sub["chemotaxis"], 0
                              ).astype(dtype)
    f = ref.contact_forces(pos, s["diameter"] / 2, alive, build_ijk, n,
                           cfg["force"]["repulsion_k"],
                           cfg["force"]["attraction_gamma"], dtype)
    pos = jnp.clip(pos + (f * dt).astype(dtype), lo, hi).astype(dtype)
    for sub in cfg["substances"]:
        grids[sub["name"]] = ref.diffuse(grids[sub["name"]], sub["diffusion"],
                                         sub["decay"], dt, geo[sub["name"]][1])
    attrs = dict(s["attrs"])
    attrs[PRE_DOSE] = attrs["exposure"]
    attrs["exposure"] = dosed(cfg, attrs[PRE_DOSE], grids, pos, kind, alive,
                              dtype)
    return dict(s, position=pos, grids=grids, attrs=attrs,
                age=s["age"] + jnp.where(alive, dt, 0), step=s["step"] + 1)


def reference(cfg: dict, s: dict, n_steps: int, dtype=jnp.float32) -> dict:
    s = common.on_device(s)
    for _ in range(n_steps):
        s = step(cfg, s, dtype)
    return common.to_host(s)


def compare(cfg: dict, got: dict, want: dict) -> dict:
    g, w = common.by_tag(got), common.by_tag(want)
    subst = max(common.rel_gap(got["grids"][k], want["grids"][k])
                for k in want["grids"])
    grids = {k: jnp.asarray(v) for k, v in want["grids"].items()}
    exposure = dosed(cfg, jnp.asarray(w["attrs"][PRE_DOSE]), grids,
                     jnp.asarray(g["position"]), jnp.asarray(w["kind"]),
                     True, jnp.float32)
    return {
        "layout_mismatch": common.layout_mismatch(got, want),
        "position_gap": float(np.abs(g["position"] - w["position"]).max()),
        "substance_gap": subst,
        "exposure_gap": common.rel_gap(g["attrs"]["exposure"],
                                       np.asarray(exposure)),
    }
