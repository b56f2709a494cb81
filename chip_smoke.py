"""Smoke run of the Simulation facade's main path on a TPU.

Drives the soma-clustering model of ``examples/quickstart.py`` through the
normal entry points (``Simulation`` → ``build()`` → ``run_jit``, and
``Simulation.distribute`` for the mesh) with the fused cell-list force
kernel, and checks what comes out.

    python chip_smoke.py                # one chip: main + parity phases
    python chip_smoke.py --four-chips   # 2x2 mesh: distributed phase only
    JAX_PLATFORMS=cpu python chip_smoke.py --agents 512   # CPU rehearsal

Phases (all in this one process; any failure exits non-zero):

* main — ``--agents`` (default 2^19) agents at the quickstart's density,
  10-unit cells with ``max_per_cell=16``, two substances on 5-unit voxels,
  ``impl="fused"``: one warm-up ``run_jit`` call, then ``--steps`` steps
  ending in ``block_until_ready``.  Checks finite positions, a conserved
  alive count, a clean HealthReport and ``cell_overflow_steps == 0`` (so
  the fused kernel, not its dense fallback, computed every step).
* parity — the same model at min(2^14, --agents) agents for 10 steps with
  ``impl="fused"``, ``impl="fused", tile_order="morton"`` and
  ``impl="reference"``, compared by :func:`check_close`.
* four chips (``--four-chips`` only) — ``Simulation.distribute`` on a
  (2, 2) mesh at ``--agents`` agents per chip for 10 steps (agents
  conserved, health clean), then two comparisons against one chip at
  min(2^14, --agents) agents:

  - halo: the model's agents and contact mechanics without substances,
    10 steps, every agent matched by tag and compared by
    :func:`check_close` — over the f32 halo wire (``halo_codec="none"``)
    to ``ATOL``, over the default int16 wire to :func:`codec_atol`.  Agents
    with a contact partner across an internal face (ghost readers) are
    counted and must exist, so the halo exchange and its codec are on the
    compared path;
  - substances: one step of the full model; agents at least
    ``SUBSTANCE_BAND`` from an internal face agree within ``ATOL``, and
    the error of those inside the band is printed (an open gap: secretion
    and gradient sampling do not read the neighbour's voxels).

The seconds printed are one informational run, not a benchmark.  The last
line is ``{"ok": true, "device": {...}}`` — printed only on a TPU.  On any
other platform the phases run only when ``--agents`` is given (a CPU
rehearsal) and the script exits 1 without that line.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from examples.quickstart import build_model  # noqa: E402
from repro import Simulation  # noqa: E402
from repro.core import ForceParams  # noqa: E402
from repro.core.distributed import DomainConfig  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402

MAIN_AGENTS = 1 << 19
PARITY_AGENTS = 1 << 14
PARITY_STEPS = 10
# The quickstart's density: 600 agents in the 80³ interior of its 100-unit
# space (agents are placed 10 units clear of each face).
DENSITY = 600 / 80.0**3
CELL = 10.0        # build_model's cell_size (the interaction radius)
VOXEL = 5.0        # the quickstart's substance voxel width
MAX_PER_CELL = 16
# Position tolerances (space units; agents have diameter 5), see
# check_close.  The paths differ only in float summation order (and, across
# chips, the int16 halo codec).  With dt = 1 the contact dynamics of dense
# clusters is unstable: such differences grow 2-4x per step, so after 10
# steps a few agents in 10^3 sit up to ~1 unit apart while the rest agree
# to ~1e-4 (CPU, 2^14 agents).
ATOL = 1e-3            # every agent after step 1
SHARE_WITHIN = 0.99    # share of agents within ATOL after the last step
# Over the int16 halo wire a ghost sits up to half a quantum from its f32
# position (quantum: DomainConfig.codec_span / 32767 per dim).  A ghost
# reader's contact force turns that into up to ~2.2 half-quanta of position
# error in one step (CPU, 2^12 and 2^14 agents: 5.6e-3 and 8.7e-3); the
# bound is CODEC_HALF_QUANTA of them (0.020 at 2^14 agents).
CODEC_HALF_QUANTA = 5
# Secretion and gradient sampling clip at each chip's substance grid edge
# instead of reading the neighbour's voxels (trilinear reach ~2 voxels), so
# agents this close to an internal face leave the one-chip trajectory of
# the substance model.  The halo comparison covers them without substances.
SUBSTANCE_BAND = 3 * VOXEL


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def space_for(n: int) -> float:
    """Edge of the cubic space holding ``n`` agents at DENSITY (+10 margins)."""
    return 20.0 + (n / DENSITY) ** (1.0 / 3.0)


def soma(n: int, space: float | None = None, **mechanics):
    space = space_for(n) if space is None else space
    return build_model(
        n, space, seed=0, max_per_cell=MAX_PER_CELL,
        resolution=round(space / VOXEL), **mechanics,
    )


def check_state(state, n: int, label: str) -> None:
    """Finite live positions, ``n`` live agents, every health counter 0."""
    alive = np.asarray(state.pool.alive)
    pos = np.asarray(state.pool.position)
    check(int(alive.sum()) == n, f"{label}: {int(alive.sum())} alive != {n}")
    check(bool(np.isfinite(pos[alive]).all()), f"{label}: non-finite positions")
    for f in dataclasses.fields(state.health):
        v = int(np.asarray(getattr(state.health, f.name)).sum())
        check(v == 0, f"{label}: health.{f.name} = {v}")


def live_positions(state) -> np.ndarray:
    return np.asarray(state.pool.position)[np.asarray(state.pool.alive)]


def phase_main(n: int, steps: int) -> None:
    built = soma(n, impl="fused").build()
    print(f"main: {n} agents, grid {built.config.spec.dims} cells, "
          f"max_per_cell {MAX_PER_CELL}, impl=fused", flush=True)
    t0 = time.perf_counter()
    warm, _ = built.run_jit(steps)
    jax.block_until_ready(warm)
    t1 = time.perf_counter()
    final, _ = built.run_jit(steps, state=warm)
    jax.block_until_ready(final)
    t2 = time.perf_counter()
    print(f"main: warm-up call (compile + {steps} steps) {t1 - t0} s", flush=True)
    print(f"main: steady {(t2 - t1) / steps} s/step over {steps} steps",
          flush=True)
    check(int(final.step) == 2 * steps, f"main: step counter {int(final.step)}")
    check_state(final, n, "main")
    print("main: OK", flush=True)


def check_close(label: str, first: np.ndarray, last: np.ndarray,
                atol: float = ATOL) -> None:
    """Per-agent position errors after step 1 (``first``) and after the
    last step (``last``): every agent within ``atol`` after one step (a
    wrong force anywhere shows at once), and SHARE_WITHIN of them after the
    last (later steps amplify rounding chaotically in dense clusters)."""
    err1 = float(first.max())
    share = float((last <= atol).mean())
    print(f"{label}: after step 1 max error {err1}; after step "
          f"{PARITY_STEPS} {share} of agents within {atol} (max "
          f"{float(last.max())}, median {float(np.median(last))})", flush=True)
    check(err1 <= atol, f"{label}: step-1 error {err1} > {atol}")
    check(share >= SHARE_WITHIN, f"{label}: only {share} within {atol}")


def stepwise(run_one, state, steps: int):
    """States after each of ``steps`` single-step calls (one compile)."""
    out = []
    for _ in range(steps):
        state, _ = run_one(state)
        out.append(state)
    return out


def phase_parity(n: int) -> None:
    # morton_window spans the whole pool: the default window does not cover
    # a uniform pool at these sizes, and the coverage fallback would then
    # run the linear kernel in its place.
    variants = {
        "fused": dict(impl="fused"),
        "fused-morton": dict(impl="fused", tile_order="morton",
                             morton_window=-(-n // 128)),
        "reference": dict(impl="reference"),
    }
    traj = {}
    for label, opts in variants.items():
        built = soma(n, **opts).build()
        traj[label] = stepwise(
            lambda st: built.run_jit(1, state=st), built.state, PARITY_STEPS
        )
        check_state(traj[label][-1], n, f"parity/{label}")
    ref = traj["reference"]
    for label in ("fused", "fused-morton"):
        err = [np.abs(np.asarray(traj[label][k].pool.position)
                      - np.asarray(ref[k].pool.position)).max(1)
               for k in (0, -1)]
        check_close(f"parity: {n} agents, {label} vs reference", *err)
    print("parity: OK", flush=True)


def domain(space: float, n_per_chip: int) -> DomainConfig:
    """(2, 2) decomposition of x and y; z stays whole.  Buffers hold 2x the
    expected agents in a 10-unit face slab (halo) and a 2-unit one
    (per-step migration)."""
    extent = space / 2
    slab = extent * space * DENSITY   # expected agents per unit of thickness
    return DomainConfig(
        mesh_axes=("data", "model"), axis_sizes=(2, 2), extent=extent,
        halo_width=CELL, depth=space,
        halo_capacity=int(2 * CELL * slab) + 64,
        migrate_capacity=int(2 * 2.0 * slab) + 64,
    )


def global_positions(dcfg: DomainConfig, state) -> np.ndarray:
    """Live positions of a stacked DistState in global coordinates."""
    pos = np.asarray(state.pool.position)
    alive = np.asarray(state.pool.alive)
    out = []
    for dev in range(pos.shape[0]):
        q = pos[dev][alive[dev]].copy()
        for d, c in enumerate(dcfg.device_coords(dev)):
            q[:, d] += c * dcfg.extent
        out.append(q)
    return np.concatenate(out)


def internal_face_distance(dcfg: DomainConfig, pos: np.ndarray) -> np.ndarray:
    """Distance from each global position to the nearest internal face."""
    d = np.full(len(pos), np.inf)
    for dim, size in enumerate(dcfg.axis_sizes):
        for k in range(1, size):
            d = np.minimum(d, np.abs(pos[:, dim] - k * dcfg.extent))
    return d


def matched_error(ref: np.ndarray, got: np.ndarray,
                  one_to_one: bool = True) -> np.ndarray:
    """Per-agent max-coordinate distance from each ``ref`` agent to its
    nearest ``got`` agent (the engines store agents in different slot
    orders, so agents match by position); with ``one_to_one`` no two
    ``ref`` agents may match the same ``got`` agent."""
    nearest, dist = [], []
    for chunk in np.array_split(ref, max(1, len(ref) // 512)):
        d = np.abs(chunk[:, None, :] - got[None, :, :]).max(-1)
        i = d.argmin(1)
        nearest.append(i)
        dist.append(d[np.arange(len(i)), i])
    check(not one_to_one or len(np.unique(np.concatenate(nearest))) == len(ref),
          "four-chips: agents do not match one-to-one")
    return np.concatenate(dist)


def contact_only(n: int, space: float) -> Simulation:
    """The soma model's agents (same positions and kinds) with contact
    mechanics and nothing else, each tagged with its index: what crosses
    an internal face is then only the halo."""
    rng = np.random.default_rng(0)
    pos = rng.uniform(10, space - 10, (n, 3)).astype(np.float32)
    kind = (rng.random(n) < 0.5).astype(np.int32)
    return (
        Simulation(space=(0.0, space), cell_size=CELL, boundary="closed",
                   dt=1.0, max_per_cell=MAX_PER_CELL, seed=0)
        .add_agents(n, position=pos, diameter=5.0, kind=kind,
                    tag=np.arange(n, dtype=np.int32))
        .mechanics(ForceParams(), impl="fused")
    )


def by_tag(pos: np.ndarray, tag: np.ndarray, n: int) -> np.ndarray:
    """Positions reordered by agent tag (every tag 0..n-1 exactly once)."""
    check(np.array_equal(np.sort(tag), np.arange(n)),
          "four-chips: agent tags lost or duplicated")
    out = np.empty((n, 3), pos.dtype)
    out[tag] = pos
    return out


def single_tagged(state, n: int) -> np.ndarray:
    alive = np.asarray(state.pool.alive)
    return by_tag(np.asarray(state.pool.position)[alive],
                  np.asarray(state.pool.attrs["tag"])[alive], n)


def dist_tagged(dcfg: DomainConfig, state, n: int) -> np.ndarray:
    alive = np.asarray(state.pool.alive)
    return by_tag(global_positions(dcfg, state),
                  np.asarray(state.pool.attrs["tag"])[alive], n)


def ghost_readers(dcfg: DomainConfig, pos: np.ndarray,
                  reach: float) -> np.ndarray:
    """Mask of agents with a partner within ``reach`` on the other side of
    an internal face: their forces need the halo."""
    dev = np.zeros(len(pos), np.int64)
    for dim, size in enumerate(dcfg.axis_sizes):
        c = np.clip((pos[:, dim] // dcfg.extent).astype(np.int64), 0, size - 1)
        dev = dev * size + c
    near = internal_face_distance(dcfg, pos) < reach
    idx = np.nonzero(near)[0]
    out = np.zeros(len(pos), bool)
    for chunk in np.array_split(idx, max(1, len(idx) // 512)):
        d = np.abs(pos[chunk, None, :] - pos[None, idx, :])
        close = (d ** 2).sum(-1) < reach ** 2
        out[chunk] = (close & (dev[chunk, None] != dev[None, idx])).any(1)
    return out


def phase_four_chips(n_per_chip: int) -> None:
    mesh = make_mesh((2, 2), ("data", "model"))
    n = 4 * n_per_chip
    space = space_for(n)
    dcfg = domain(space, n_per_chip)
    cap = n_per_chip + n_per_chip // 8 + 64
    dsim = soma(n, space, impl="fused").distribute(mesh, dcfg, capacity=cap)
    print(f"four-chips: {n} agents ({n_per_chip}/chip), local grid "
          f"{dsim.config.spec.dims} cells, capacity {cap}/chip", flush=True)
    t0 = time.perf_counter()
    final, _ = dsim.run(PARITY_STEPS)
    jax.block_until_ready(final)
    print(f"four-chips: {PARITY_STEPS} steps incl. compile "
          f"{time.perf_counter() - t0} s", flush=True)
    check_state(final, n, "four-chips")

    m = min(PARITY_AGENTS, n_per_chip)
    space = space_for(m)
    dcfg = domain(space, m // 4)
    phase_halo(mesh, dcfg, m, space)
    phase_substances(mesh, dcfg, m, space)
    print("four-chips: OK", flush=True)


def codec_atol(dcfg: DomainConfig) -> float:
    return CODEC_HALF_QUANTA * max(dcfg.codec_span) / 32767 / 2


def phase_halo(mesh, dcfg: DomainConfig, m: int, space: float) -> None:
    built = contact_only(m, space).build()
    single = stepwise(lambda st: built.run_jit(1, state=st), built.state,
                      PARITY_STEPS)
    check_state(single[-1], m, "halo/single")
    start = single_tagged(built.state, m)
    readers = ghost_readers(dcfg, start, 5.0)   # contact: diameter 5
    moved = np.abs(single_tagged(single[0], m) - start).max(1)
    print(f"halo: {m} agents, {int(readers.sum())} with a contact partner "
          f"across an internal face; they move up to "
          f"{float(moved[readers].max())} in step 1", flush=True)
    check(int(readers.sum()) > 0, "halo: no agent reads a ghost")
    ref = [single_tagged(single[k], m) for k in (0, -1)]
    for wire, atol in (("none", ATOL), ("int16", codec_atol(dcfg))):
        dc = dataclasses.replace(dcfg, halo_codec=wire)
        dsim = contact_only(m, space).distribute(mesh, dc,
                                                 capacity=m // 2 + 64)
        dist = stepwise(lambda st: (dsim.step(st), None), dsim.state,
                        PARITY_STEPS)
        check_state(dist[-1], m, f"halo/{wire}")
        err = [np.abs(dist_tagged(dc, dist[k], m) - r).max(1)
               for k, r in zip((0, -1), ref)]
        print(f"halo: {wire} wire, ghost readers' step-1 max error "
              f"{float(err[0][readers].max())}", flush=True)
        check_close(f"halo: {m} agents, {wire} wire vs one chip", *err,
                    atol=atol)


def phase_substances(mesh, dcfg: DomainConfig, m: int, space: float) -> None:
    single, _ = soma(m, space, impl="fused").build().run_jit(1)
    check_state(single, m, "substances/single")
    dist, _ = soma(m, space, impl="fused").distribute(
        mesh, dcfg, capacity=m // 2 + 64
    ).run(1)
    check_state(dist, m, "substances/dist")
    ref = live_positions(single)
    got = global_positions(dcfg, dist)
    check(len(got) == m, f"substances: {len(got)} distributed agents")
    far = internal_face_distance(dcfg, ref) >= SUBSTANCE_BAND
    err = float(matched_error(ref[far], got).max())
    # Inside the band agents may drift past each other, so they match by
    # nearest position only: a lower bound on their error.
    band = float(matched_error(ref[~far], got, one_to_one=False).max())
    print(f"substances: {m} agents, 1 step, distributed vs one chip: max "
          f"error {err} over the {int(far.sum())} agents >= {SUBSTANCE_BAND} "
          f"from an internal face (atol {ATOL}); at least {band} over the "
          f"{int((~far).sum())} inside (not compared: open gap)", flush=True)
    check(far.mean() > 0.5, "substances: too few agents away from faces")
    check(err <= ATOL, f"substances: distributed off by {err}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--agents", type=int, default=None,
                    help=f"main-phase agents (per chip with --four-chips; "
                         f"default {MAIN_AGENTS})")
    ap.add_argument("--steps", type=int, default=20,
                    help="timed main-phase steps (default 20)")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the distributed phase on a (2, 2) mesh")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}",
          flush=True)
    on_tpu = dev.platform == "tpu"
    if not on_tpu and args.agents is None:
        print(f"no TPU ({dev.platform} only); pass --agents N to rehearse "
              f"the phases here", file=sys.stderr)
        return 1
    n = MAIN_AGENTS if args.agents is None else args.agents

    if args.four_chips:
        check(len(devices) >= 4, f"--four-chips needs 4 devices, "
                                 f"found {len(devices)}")
        phase_four_chips(n)
    else:
        phase_main(n, args.steps)
        phase_parity(min(PARITY_AGENTS, n))

    if not on_tpu:
        print(f"phases passed on {dev.platform}, which is not a TPU: "
              f"no result", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
