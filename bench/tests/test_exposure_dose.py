"""The soma check doses the reference's exposure at the program's own
end-of-step positions: a position one ulp across a voxel face from the
reference's reads the same exposure gap as one that agrees, and a dose that
is off still fails."""

import jax.numpy as jnp
import numpy as np

import population
import reference as ref
import run as harness
from checks import soma_clustering as soma

AGENTS = 64
MOVED = 5


def face_pair(origin: float, spacing: float, res: int):
    """Two neighbouring float32 coordinates, one ulp apart, that lie in
    neighbouring voxels (the face between voxels 2 and 3)."""
    x = np.float32(origin + 3 * spacing)
    for _ in range(32):
        x = np.nextafter(x, np.float32(-np.inf))
    voxel = lambda v: int(ref.nearest_voxel(jnp.float32(v), origin, spacing,
                                            res))
    while True:
        up = np.nextafter(x, np.float32(np.inf))
        if voxel(up) != voxel(x):
            return x, up
        x = up


def states():
    """The reference's end-of-step state and the program's: identical but
    for one agent, whose x lies one ulp across a voxel face, with the
    program's exposure dosed at its own position."""
    cfg = population.resized(harness.read_json("configs",
                                               "soma_clustering.json"), AGENTS)
    lo, hi = cfg["space"]
    rng = np.random.default_rng(0)
    pos = rng.uniform(lo + cfg["margin"], hi - cfg["margin"],
                      (AGENTS, 3)).astype(np.float32)
    kind = (np.arange(AGENTS) % 2).astype(np.int32)
    alive = np.ones(AGENTS, bool)
    res = cfg["substances"][0]["resolution"]
    spacing = (hi - lo) / res
    x_ref, x_got = face_pair(lo, spacing, res)
    pos[MOVED, 0] = x_ref
    grids = {s["name"]: rng.uniform(0.5, 1.5, (res,) * 3).astype(np.float32)
             for s in cfg["substances"]}
    # The two voxels the moved agent may read hold well-separated doses.
    own = next(s for s in cfg["substances"] if s["kind"] == kind[MOVED])
    v = np.asarray(ref.nearest_voxel(jnp.asarray(pos[MOVED]), lo, spacing, res))
    grids[own["name"]][tuple(v)] = 0.5
    grids[own["name"]][(v[0] + 1, v[1], v[2])] = 1.5
    pre = rng.uniform(10, 11, AGENTS).astype(np.float32)
    tag = rng.permutation(AGENTS).astype(np.int32)

    def state(p):
        exposure = np.asarray(soma.dosed(cfg, jnp.asarray(pre), grids,
                                         jnp.asarray(p), kind, alive,
                                         jnp.float32))
        return {"position": p, "kind": kind, "alive": alive, "grids": grids,
                "attrs": {"tag": tag, "exposure": exposure,
                          soma.PRE_DOSE: pre}}

    moved = pos.copy()
    moved[MOVED, 0] = x_got
    return cfg, state(moved), state(pos), (lo, spacing, res)


def gap_where_each_stands(got, want):
    """The exposure gap with each side's dose taken at its own position."""
    return float(np.abs(got["attrs"]["exposure"]
                        - want["attrs"]["exposure"]).max()
                 / np.abs(want["attrs"]["exposure"]).max())


def test_one_ulp_across_a_voxel_face_reads_no_exposure_gap():
    cfg, got, want, geo = states()
    a, b = (ref.nearest_voxel(jnp.asarray(s["position"][MOVED]), *geo)
            for s in (got, want))
    assert int(a[0]) != int(b[0])
    limit = soma.LIMITS["exposure_gap"]
    # Dosed at the reference's own position, the flip alone fails the limit.
    assert gap_where_each_stands(got, want) > limit
    gaps = soma.compare(cfg, got, want)
    assert gaps["exposure_gap"] <= limit, gaps
    assert gaps["position_gap"] <= soma.LIMITS["position_gap"], gaps
    assert gaps["layout_mismatch"] == 0


def test_an_exposure_one_percent_off_fails():
    cfg, got, want, _ = states()
    got["attrs"]["exposure"] = got["attrs"]["exposure"].copy()
    got["attrs"]["exposure"][MOVED] *= np.float32(1.01)
    gaps = soma.compare(cfg, got, want)
    assert gaps["exposure_gap"] > soma.LIMITS["exposure_gap"], gaps

