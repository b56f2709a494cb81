"""Multi-device scenarios executed in a subprocess (needs fake CPU devices).

Run as:  python tests/dist_scenarios.py <scenario>
Exits 0 on success; prints diagnostics.  Kept out of pytest collection —
tests/test_distributed.py spawns it with XLA_FLAGS set.
"""

import dataclasses
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import (  # noqa: E402
    EngineConfig,
    ForceParams,
    init_state,
    make_pool,
    run_jit,
    spec_for_space,
)
from repro.core.distributed import (  # noqa: E402
    DomainConfig,
    global_kind_counts,
    halo_wire_stats,
    init_dist_state,
    make_distributed_step,
)


def _mesh(shape, names):
    from repro.launch.mesh import make_mesh  # jax-version-compat axis_types

    return make_mesh(shape, names)


def _force_only_setup(halo_codec):
    """Deterministic (no-RNG) force relaxation on a 4×2 device grid."""
    extent, halo = 16.0, 2.0
    mesh = _mesh((4, 2), ("data", "model"))
    dcfg = DomainConfig(
        mesh_axes=("data", "model"),
        axis_sizes=(4, 2),
        extent=extent,
        halo_width=halo,
        halo_capacity=96,
        migrate_capacity=48,
        depth=16.0,
        halo_codec=halo_codec,
    )
    spec = dcfg.grid_spec(box_size=2.0, max_per_cell=32)
    ecfg = EngineConfig(
        spec=spec,
        behaviors=(),
        force_params=ForceParams(),
        dt=0.05,
        min_bound=0.0,
        max_bound=extent,
        boundary="open",
        sort_frequency=4,
    )
    rng = np.random.default_rng(42)
    n = 500
    # Interior margin keeps the parity comparison clean: the distributed
    # space is a torus (+ closed z), the single-node reference is open —
    # identical physics only while no agent touches a global boundary.
    pos = rng.uniform(2.0, [4 * extent - 2.0, 2 * extent - 2.0, 14.0], (n, 3)).astype(
        np.float32
    )
    return mesh, dcfg, ecfg, pos, n


def _single_node_reference(
    pos, n_steps, dt=0.05, force_impl="reference", box=2.0, max_per_cell=32
):
    """Same physics on one device in global coordinates (open z, toroidal
    x/y is irrelevant here: diameter 1.6 agents stay far from edges).

    ``box``/``max_per_cell`` only change the grid resolution, not the
    physics (any box ≥ the 1.6 interaction diameter yields a candidate
    superset); the fused reference uses a coarser grid because interpret-
    mode kernel cost scales with the program count (n_cols × 9)."""
    n = pos.shape[0]
    pool = make_pool(n, jnp.asarray(pos), diameter=1.6)
    spec = spec_for_space(0.0, 64.0, box, max_per_cell=max_per_cell)
    ecfg = EngineConfig(
        spec=spec,
        behaviors=(),
        force_params=ForceParams(),
        dt=dt,
        min_bound=0.0,
        max_bound=64.0,
        boundary="open",
        sort_frequency=4,
        force_impl=force_impl,
    )
    state = init_state(pool)
    final, _ = run_jit(ecfg, state, n_steps)
    return np.asarray(final.pool.position), np.asarray(final.pool.alive)


def _global_positions(dcfg, state):
    """Recover global coordinates from the stacked local frames."""
    p = np.asarray(state.pool.position)  # (n_dev, C, 3)
    a = np.asarray(state.pool.alive)
    n_dev = p.shape[0]
    out = []
    for dev in range(n_dev):
        cx, cy = divmod(dev, dcfg.axis_sizes[1])
        q = p[dev][a[dev]].copy()
        q[:, 0] += cx * dcfg.extent
        q[:, 1] += cy * dcfg.extent
        out.append(q)
    return np.concatenate(out, axis=0)


def scenario_conservation():
    mesh, dcfg, ecfg, pos, n = _force_only_setup("int16")
    state = init_dist_state(dcfg, capacity=192, positions=pos, diameter=1.6)
    step = make_distributed_step(mesh, dcfg, ecfg)
    for _ in range(30):
        state = step(state)
    alive = int(np.asarray(state.pool.alive).sum())
    assert alive == n, f"population changed: {alive} != {n}"
    assert int(np.asarray(state.migrate_overflow).sum()) == 0
    assert int(np.asarray(state.halo_overflow).sum()) == 0
    print("conservation OK")


def scenario_parity_simple(codec="int16", tol=1e-3):
    """Distributed relaxation must match the single-node engine agent-by-
    agent (matched by nearest neighbor, since orderings differ)."""
    mesh, dcfg, ecfg, pos, n = _force_only_setup(codec)
    n_steps = 20
    state = init_dist_state(dcfg, capacity=192, positions=pos, diameter=1.6)
    step = make_distributed_step(mesh, dcfg, ecfg)
    for _ in range(n_steps):
        state = step(state)
    dist_pos = _global_positions(dcfg, state)

    ref_pos, ref_alive = _single_node_reference(pos, n_steps, dt=ecfg.dt)
    ref = ref_pos[ref_alive]

    assert dist_pos.shape[0] == ref.shape[0] == n
    # brute-force nearest match (n is small)
    d = np.linalg.norm(dist_pos[:, None, :] - ref[None, :, :], axis=-1)
    nearest = d.min(axis=1)
    worst = float(nearest.max())
    print(f"codec={codec}: worst agent deviation vs single-node = {worst:.5f}")
    assert worst < tol, f"parity violated: {worst} >= {tol}"
    # every reference agent is matched by someone (bijectivity proxy)
    assert len(set(d.argmin(axis=1).tolist())) == n
    print("parity OK")


def scenario_codec_reduction():
    """int16/int8 halo codecs must not change physics beyond their bound."""
    results = {}
    for codec in ("none", "int16", "int8"):
        mesh, dcfg, ecfg, pos, n = _force_only_setup(codec)
        state = init_dist_state(dcfg, capacity=192, positions=pos, diameter=1.6)
        step = make_distributed_step(mesh, dcfg, ecfg)
        for _ in range(15):
            state = step(state)
        results[codec] = _global_positions(dcfg, state)
        results[codec] = results[codec][np.lexsort(results[codec].T)]
    err16 = np.abs(results["int16"] - results["none"]).max()
    err8 = np.abs(results["int8"] - results["none"]).max()
    print(f"max deviation: int16={err16:.5f} int8={err8:.5f}")
    assert err16 < 1e-3, err16
    assert err8 < 2e-2, err8
    print("codec reduction OK")


def _fused_ecfg(ecfg, fallback=False):
    return dataclasses.replace(ecfg, force_impl="fused", fused_overflow_fallback=fallback)


def scenario_fused_parity(tol_dense=5e-4, tol_single=1e-3):
    """Distributed fused force pass (DESIGN.md §4 adoption) vs (a) the dense
    distributed path — slot-aligned, differing only by float summation order —
    and (b) the single-node fused engine (nearest-match, §6.3.3 style).

    The layout plants clusters straddling device *corners* (x and y device
    boundaries simultaneously) so corner-halo agents — the multi-phase
    routing's hardest case — carry real forces through the fused kernel.
    """
    mesh, dcfg, ecfg, pos, n = _force_only_setup("int16")
    rng = np.random.default_rng(3)
    # Clusters of overlapping agents centered on device-corner junctions.
    corners = [(16.0, 16.0), (32.0, 16.0), (48.0, 16.0)]
    extra = []
    for cx, cy in corners:
        extra.append(
            np.stack(
                [
                    rng.uniform(cx - 1.5, cx + 1.5, 24),
                    rng.uniform(cy - 1.5, cy + 1.5, 24),
                    rng.uniform(4.0, 12.0, 24),
                ],
                axis=1,
            )
        )
    pos = np.concatenate([pos] + extra).astype(np.float32)
    n = pos.shape[0]
    n_steps = 8

    state0 = init_dist_state(dcfg, capacity=256, positions=pos, diameter=1.6)
    finals = {}
    for name, cfg in (("dense", ecfg), ("fused", _fused_ecfg(ecfg))):
        step = make_distributed_step(mesh, dcfg, cfg)
        s = state0
        for _ in range(n_steps):
            s = step(s)
        assert int(np.asarray(s.pool.alive).sum()) == n, name
        assert int(np.asarray(s.halo_overflow).sum()) == 0, name
        finals[name] = s
    # (a) slot-aligned distributed dense vs fused.
    d = np.abs(
        np.asarray(finals["dense"].pool.position)
        - np.asarray(finals["fused"].pool.position)
    ).max()
    print(f"max slot-aligned |dense - fused| after {n_steps} steps = {d:.2e}")
    assert d < tol_dense, d

    # (b) nearest-match parity vs the single-node *fused* engine.
    dist_pos = _global_positions(dcfg, finals["fused"])
    ref_pos, ref_alive = _single_node_reference(
        pos, n_steps, force_impl="fused", box=4.0, max_per_cell=48
    )
    ref = ref_pos[ref_alive]
    assert dist_pos.shape[0] == ref.shape[0] == n
    dmat = np.linalg.norm(dist_pos[:, None, :] - ref[None, :, :], axis=-1)
    worst = float(dmat.min(axis=1).max())
    print(f"worst agent deviation vs single-node fused = {worst:.5f}")
    assert worst < tol_single, worst
    assert len(set(dmat.argmin(axis=1).tolist())) == n
    print("fused parity OK")


def scenario_fused_dead_agents(tol=5e-4):
    """Dead pool slots must stay invisible to the fused path exactly as they
    are to the dense one (they never enter the halo-extended cell list)."""
    mesh, dcfg, ecfg, pos, n = _force_only_setup("int16")
    state0 = init_dist_state(dcfg, capacity=192, positions=pos, diameter=1.6)
    # Kill a deterministic scattering of slots on every device.
    alive = np.asarray(state0.pool.alive).copy()
    kill = np.zeros_like(alive)
    kill[:, 3::17] = True
    alive &= ~kill
    state0 = dataclasses.replace(
        state0, pool=state0.pool.replace(alive=jnp.asarray(alive))
    )
    n_alive = int(alive.sum())

    finals = {}
    for name, cfg in (("dense", ecfg), ("fused", _fused_ecfg(ecfg))):
        step = make_distributed_step(mesh, dcfg, cfg)
        s = state0
        for _ in range(10):
            s = step(s)
        assert int(np.asarray(s.pool.alive).sum()) == n_alive, name
        finals[name] = _global_positions(dcfg, s)
    a = finals["dense"][np.lexsort(finals["dense"].T)]
    b = finals["fused"][np.lexsort(finals["fused"].T)]
    d = np.abs(a - b).max()
    print(f"dead-agent run: {n_alive}/{n} alive, max |dense - fused| = {d:.2e}")
    assert d < tol, d
    print("fused dead agents OK")


def scenario_fused_overflow_fallback():
    """Cell-list overflow on the halo-extended grid must flip the fused path
    onto its lax.cond dense fallback, reproducing the dense distributed step
    exactly (same candidate computation, same summation order)."""
    mesh, dcfg, ecfg, pos, n = _force_only_setup("int16")
    # Overcrowd one box: 12 agents inside a single 2.0-cell on device (0, 0),
    # with max_per_cell=4 the halo-extended index overflows every step.
    spec = dcfg.grid_spec(box_size=2.0, max_per_cell=4)
    ecfg = dataclasses.replace(ecfg, spec=spec, dt=0.01)
    rng = np.random.default_rng(9)
    blob = rng.uniform(5.0, 6.5, (12, 3)).astype(np.float32)
    pos = np.concatenate([pos, blob]).astype(np.float32)
    n = pos.shape[0]

    state0 = init_dist_state(dcfg, capacity=256, positions=pos, diameter=1.6)
    finals = {}
    for name, cfg in (("dense", ecfg), ("fused_fb", _fused_ecfg(ecfg, fallback=True))):
        step = make_distributed_step(mesh, dcfg, cfg)
        s = state0
        for _ in range(3):
            s = step(s)
        finals[name] = np.asarray(s.pool.position)
    np.testing.assert_allclose(finals["dense"], finals["fused_fb"], atol=0.0)
    print("fused overflow fallback OK")


def scenario_telemetry():
    """§6.2.2/§6.2.3 observability: DistState carries exact cumulative wire
    bytes (incl. ceil-rounded bitmask sizes, the //8→0 truncation fix) and
    the halo_overflow counter trips when halo_capacity is undersized."""
    extent, halo = 16.0, 2.0
    mesh = _mesh((4, 2), ("data", "model"))
    h = 4  # tiny: bitmasks are sub-byte (ceil → 1), capacity overflows
    dcfg = DomainConfig(
        mesh_axes=("data", "model"),
        axis_sizes=(4, 2),
        extent=extent,
        halo_width=halo,
        halo_capacity=h,
        migrate_capacity=48,
        depth=16.0,
        halo_codec="int16",
    )
    spec = dcfg.grid_spec(box_size=2.0, max_per_cell=32)
    ecfg = EngineConfig(
        spec=spec, behaviors=(), force_params=ForceParams(), dt=0.05,
        min_bound=0.0, max_bound=extent, boundary="open", sort_frequency=4,
    )
    rng = np.random.default_rng(42)
    pos = rng.uniform(2.0, [4 * extent - 2.0, 2 * extent - 2.0, 14.0], (500, 3))
    state = init_dist_state(dcfg, capacity=192, positions=pos.astype(np.float32),
                            diameter=1.6)
    step = make_distributed_step(mesh, dcfg, ecfg)
    n_steps = 5
    for _ in range(n_steps):
        state = step(state)

    # int16 channel: q 2B×3, rad f32, kind i8, fresh/valid 1-bit → ceil 1 B.
    per_channel = h * 3 * 2 + (h + 7) // 8 + h * 4 + h + (h + 7) // 8
    per_channel_base = h * 3 * 4 + h * 4 + h * 4 + (h + 7) // 8
    channels = dcfg.n_decomposed * 2
    payload = np.asarray(state.halo_payload_bytes)
    baseline = np.asarray(state.halo_baseline_bytes)
    assert (payload == n_steps * channels * per_channel).all(), payload
    assert (baseline == n_steps * channels * per_channel_base).all(), baseline
    stats = halo_wire_stats(state)
    assert stats["compression_ratio"] > 1.0, stats
    assert int(np.asarray(state.halo_overflow).sum()) > 0  # h=4 is undersized
    print(f"wire stats: {stats}")
    print("telemetry OK")


def scenario_packing_no_sort():
    """The migrate/halo packing hot path must lower with ZERO sort ops —
    selection and insertion are cumsum-rank compaction scatters now.  Since
    the §5.4.2 layout sort went sort-free too (counting-sort permutation,
    ISSUE 8), the ENTIRE distributed step must lower sort-free even with the
    sort op enabled; a standalone argsort lowering is the positive control
    proving the detector sees sorts."""
    import jax
    import jax.numpy as jnp
    from repro.core.distributed import hlo_sort_count, make_packing_program

    mesh, dcfg, ecfg, pos, n = _force_only_setup("int16")
    state = init_dist_state(dcfg, capacity=192, positions=pos, diameter=1.6)

    detector_hlo = jax.jit(jnp.argsort).lower(
        jnp.zeros((64,), jnp.float32)
    ).as_text()
    assert hlo_sort_count(detector_hlo) > 0, "detector broken: argsort unseen"

    packing_hlo = make_packing_program(mesh, dcfg).lower(state).as_text()
    n_packing = hlo_sort_count(packing_hlo)

    step_hlo = make_distributed_step(mesh, dcfg, ecfg).lower(state).as_text()
    n_step = hlo_sort_count(step_hlo)

    # ISSUE 8 acceptance: sort-free with the layout sort firing EVERY step,
    # not just cond-gated (sort_frequency=4 above).
    ecfg_sf1 = dataclasses.replace(ecfg, sort_frequency=1)
    sf1_hlo = make_distributed_step(mesh, dcfg, ecfg_sf1).lower(state).as_text()
    n_sf1 = hlo_sort_count(sf1_hlo)

    print(f"sort ops: packing={n_packing}, full step={n_step}, sf=1 {n_sf1}")
    assert n_step == 0, f"{n_step} sort ops left in the full distributed step"
    assert n_sf1 == 0, f"{n_sf1} sort ops in the sf=1 distributed step"
    assert n_packing == 0, f"{n_packing} sort ops left in migrate/halo packing"
    print("packing sort-free OK")


def scenario_lazy_candidates():
    """Neighbor-dataflow audit for the distributed step (the distributed
    sibling of tests/test_engine.py's candidate-count regressions): the
    dense (C, 27M) candidate tensor is built exactly once on the dense
    path, once (inside the lax.cond fallback branch) with the fused
    fallback, and NEVER on the pure fused path."""
    import repro.core.neighbors as nb

    real = nb.candidate_neighbors_arrays
    calls = {"n": 0}

    def counted(*a, **k):
        calls["n"] += 1
        return real(*a, **k)

    mesh, dcfg, ecfg, pos, n = _force_only_setup("int16")
    state = init_dist_state(dcfg, capacity=192, positions=pos, diameter=1.6)
    nb.candidate_neighbors_arrays = counted
    try:
        counts = {}
        for name, cfg in (
            ("fused", _fused_ecfg(ecfg)),
            ("fused_fallback", _fused_ecfg(ecfg, fallback=True)),
            ("dense", ecfg),
        ):
            calls["n"] = 0
            make_distributed_step(mesh, dcfg, cfg).lower(state)
            counts[name] = calls["n"]
    finally:
        nb.candidate_neighbors_arrays = real
    print("candidate builds per step trace:", counts)
    assert counts["fused"] == 0, counts
    assert counts["fused_fallback"] == 1, counts
    assert counts["dense"] == 1, counts
    print("lazy candidates OK")


def scenario_scheduler_parity():
    """DESIGN.md §5: both engines execute through ONE scheduler.  The
    distributed schedule must be the single-node schedule op-for-op, with
    distribution composed as ops: migrate + halo_exchange inserted (pre),
    env_build / boundary / diffusion replaced in place (same name, phase,
    frequency, gate) — and the §5.5 static_flags op present, the regression
    the hardcoded duplicate pipeline used to drop."""
    from repro.core.distributed import distributed_scheduler
    from repro.core.schedule import Scheduler

    mesh, dcfg, ecfg, pos, n = _force_only_setup("int16")
    single = Scheduler.default(ecfg)
    dist = distributed_scheduler(dcfg, ecfg)

    s_names = [op.name for op in single.ordered_ops()]
    d_names = [op.name for op in dist.ordered_ops()]
    inserted = {"migrate", "halo_exchange"}
    assert [x for x in d_names if x not in inserted] == s_names, (s_names, d_names)
    assert d_names.index("sort") < d_names.index("migrate") < \
        d_names.index("halo_exchange") < d_names.index("env_build")
    assert "static_flags" in d_names, "§5.5 static detection dropped again"

    # Replaced ops keep name/phase/frequency/gate — only fn differs.
    s_ops = {op.name: op for op in single.ops}
    d_ops = {op.name: op for op in dist.ops}
    for name in s_names:
        so, do = s_ops[name], d_ops[name]
        assert (so.phase, so.frequency, so.gate) == (do.phase, do.frequency, do.gate), name
    # Shared ops come from the single scheduler module's factories (one
    # implementation, no distributed fork); only the three replaced ops and
    # the two inserted ones are defined by the distributed module.
    for name in d_names:
        mod = d_ops[name].fn.__module__
        if name in inserted | {"env_build", "boundary", "diffusion"}:
            assert mod == "repro.core.distributed", (name, mod)
        else:
            assert mod == "repro.core.schedule", (name, mod)
    print(f"op sequence: {d_names}")
    print("scheduler parity OK")


def scenario_static_flags_distributed():
    """The distributed step now runs §5.5 static detection: a relaxed
    configuration must accumulate static agents (the seed distributed engine
    left pool.static permanently False), and ghost-adjacent agents must stay
    conservative (never static while a live halo neighbor exists)."""
    mesh, dcfg, ecfg, pos, n = _force_only_setup("int16")
    state = init_dist_state(dcfg, capacity=192, positions=pos, diameter=1.6)
    step = make_distributed_step(mesh, dcfg, ecfg)
    for _ in range(30):
        state = step(state)
    static = np.asarray(state.pool.static)
    alive = np.asarray(state.pool.alive)
    frac = static.sum() / alive.sum()
    assert static.any(), "no agent ever went static in the distributed engine"
    assert not (static & ~alive).any(), "dead slots marked static"
    print(f"static fraction after relaxation: {frac:.2f}")
    print("distributed static flags OK")


def scenario_bounds_honored():
    """EngineConfig.min_bound/max_bound/boundary now govern the
    non-decomposed dims of the distributed step (the seed hardcoded a closed
    [0, depth] clamp): 'closed' clips z to [min_bound, max_bound], 'open'
    leaves escaping agents alone — matching the single-node boundary op."""
    import dataclasses as dc

    mesh, dcfg, ecfg, pos, n = _force_only_setup("int16")
    # One agent already outside the configured z-bounds; no forces/behaviors,
    # so only the boundary op can touch z.
    pos = pos[:32].copy()
    pos[0, 2] = 15.5
    state0 = init_dist_state(dcfg, capacity=192, positions=pos, diameter=1.6)
    z_bounds = (0.0, 12.0)

    finals = {}
    for mode in ("closed", "open"):
        cfg = dc.replace(ecfg, force_params=None, boundary=mode,
                         min_bound=z_bounds[0], max_bound=z_bounds[1])
        s = make_distributed_step(mesh, dcfg, cfg)(state0)
        z = np.asarray(s.pool.position)[..., 2][np.asarray(s.pool.alive)]
        finals[mode] = z
    assert finals["closed"].max() <= z_bounds[1] + 1e-6, finals["closed"].max()
    assert finals["open"].max() > z_bounds[1], finals["open"].max()
    print(f"z max: closed={finals['closed'].max():.2f} open={finals['open'].max():.2f}")
    print("bounds honored OK")


def scenario_facade_parity():
    """DESIGN.md §6: `Simulation.distribute` must compile onto the explicit
    distributed wiring bit-for-bit — same DomainConfig/EngineConfig, same
    scheduler, same binned initial state, same trajectories on a 2×2 mesh.
    Also smoke-checks domain-split substances (per-device local grids)."""
    from repro.core import ForceParams, Simulation
    from repro.core.distributed import (
        DomainConfig,
        init_dist_state,
        make_distributed_step,
    )
    from repro.core.engine import EngineConfig as ECfg

    extent, space = 16.0, 32.0
    mesh = _mesh((2, 2), ("data", "model"))
    dcfg = DomainConfig(
        mesh_axes=("data", "model"),
        axis_sizes=(2, 2),
        extent=extent,
        halo_width=2.0,
        halo_capacity=96,
        migrate_capacity=48,
        depth=space,
        halo_codec="int16",
    )
    rng = np.random.default_rng(11)
    n = 300
    pos = rng.uniform(1.0, space - 1.0, (n, 3)).astype(np.float32)
    n_steps = 12

    # Facade: the model declared once, deployed on the mesh.
    sim = (
        Simulation(space=(0.0, space), cell_size=2.0, boundary="open",
                   dt=0.05, max_per_cell=32, seed=3, sort_frequency=4)
        .add_agents(n, position=pos, diameter=1.6)
        .mechanics(ForceParams())
    )
    # capacity is per DEVICE and a deployment choice → passed at distribute()
    # (declaring capacity=256 on the model would reject the 300-agent group).
    dsim = sim.distribute(mesh, dcfg, capacity=256)
    f_state, _ = dsim.run(n_steps)

    # Hand-wired: the explicit layer the facade must compile onto.
    spec = dcfg.grid_spec(box_size=2.0, max_per_cell=32)
    ecfg = ECfg(
        spec=spec, behaviors=(), force_params=ForceParams(), dt=0.05,
        min_bound=0.0, max_bound=space, boundary="open", sort_frequency=4,
    )
    assert dsim.config == ecfg, "facade-derived EngineConfig drifted"
    h_state = init_dist_state(dcfg, capacity=256, positions=pos,
                              diameter=1.6, seed=3)
    step = make_distributed_step(mesh, dcfg, ecfg)
    for _ in range(n_steps):
        h_state = step(h_state)

    for name in ("position", "diameter", "kind", "alive", "static"):
        a = np.asarray(getattr(f_state.pool, name))
        b = np.asarray(getattr(h_state.pool, name))
        assert np.array_equal(a, b), f"pool.{name} not bit-exact"
    assert np.array_equal(np.asarray(f_state.rng), np.asarray(h_state.rng))
    assert int(np.asarray(f_state.pool.alive).sum()) == n

    # Substances: global description → per-device local grids that step.
    sim2 = (
        Simulation(space=(0.0, space), cell_size=2.0, boundary="open",
                   dt=0.05, max_per_cell=32, sort_frequency=4)
        .add_agents(n, position=pos, diameter=1.6)
        .add_substance("cue", diffusion=0.5, resolution=16)
        .mechanics(ForceParams())
    )
    dsim2 = sim2.distribute(mesh, dcfg, capacity=256)
    assert dsim2.state.grids["cue"].concentration.shape == (4, 8, 8, 16)
    s2, _ = dsim2.run(2)
    assert np.isfinite(np.asarray(s2.grids["cue"].concentration)).all()
    print("facade parity OK")


def scenario_multipod():
    """3D decomposition over a (2, 2, 2) mesh with a 'pod' axis."""
    mesh = _mesh((2, 2, 2), ("pod", "data", "model"))
    extent = 16.0
    dcfg = DomainConfig(
        mesh_axes=("data", "model", "pod"),
        axis_sizes=(2, 2, 2),
        extent=extent,
        halo_width=2.0,
        halo_capacity=96,
        migrate_capacity=48,
        halo_codec="int16",
    )
    spec = dcfg.grid_spec(box_size=2.0, max_per_cell=32)
    ecfg = EngineConfig(
        spec=spec,
        behaviors=(),
        force_params=ForceParams(),
        dt=0.05,
        min_bound=0.0,
        max_bound=extent,
        boundary="open",
        sort_frequency=4,
    )
    rng = np.random.default_rng(7)
    n = 400
    pos = rng.uniform(0.5, 2 * extent - 0.5, (n, 3)).astype(np.float32)
    state = init_dist_state(dcfg, capacity=192, positions=pos, diameter=1.6)
    step = make_distributed_step(mesh, dcfg, ecfg)
    for _ in range(20):
        state = step(state)
    alive = int(np.asarray(state.pool.alive).sum())
    assert alive == n, f"{alive} != {n}"
    print("multipod OK")


def scenario_health_cell_overflow():
    """DESIGN.md §7 telemetry under the distributed scheduler: an injected
    over-full cell must flip ``index.overflowed`` on exactly the device
    hosting it — surfacing as that device's ``health.cell_overflow_steps``
    through the shard_mapped health op — while the fused force's lax.cond
    dense branch keeps the trajectory bit-exact against the dense path."""
    mesh, dcfg, ecfg, pos, n = _force_only_setup("int16")
    spec = dcfg.grid_spec(box_size=2.0, max_per_cell=4)
    ecfg = dataclasses.replace(ecfg, spec=spec, dt=0.01)
    rng = np.random.default_rng(9)
    # 12 agents inside the single [4,6)³ cell of device (0,0) — interior
    # (beyond halo_width of every device boundary), so only device 0 sees it.
    blob = rng.uniform(4.2, 5.8, (12, 3)).astype(np.float32)
    pos = np.concatenate([pos, blob]).astype(np.float32)

    state0 = init_dist_state(dcfg, capacity=256, positions=pos, diameter=1.6)
    finals = {}
    for name, cfg in (("dense", ecfg),
                      ("fused_fb", _fused_ecfg(ecfg, fallback=True))):
        step = make_distributed_step(mesh, dcfg, cfg)
        s = state0
        for _ in range(3):
            s = step(s)
        finals[name] = s
    np.testing.assert_allclose(
        np.asarray(finals["dense"].pool.position),
        np.asarray(finals["fused_fb"].pool.position), atol=0.0,
    )
    for s in finals.values():
        ovf = np.asarray(s.health.cell_overflow_steps)
        assert ovf[0] == 3, f"device 0 should flag all 3 steps, got {ovf}"
        assert (ovf[1:] == 0).all(), f"only device 0 hosts the blob: {ovf}"
        assert np.asarray(s.health.nonfinite_agents).sum() == 0
    print(f"per-device cell_overflow_steps: {ovf}")
    print("distributed cell-overflow health OK")


def scenario_facade_resume():
    """Bit-exact kill-and-resume on the distributed engine: n steps straight
    == k + process death + ``DistributedSimulation.resume`` — final stacked
    DistState AND the observable series, through the facade alone."""
    import shutil
    import tempfile

    from repro.core import ForceParams, Simulation

    space = 32.0
    mesh = _mesh((2, 2), ("data", "model"))
    dcfg = DomainConfig(
        mesh_axes=("data", "model"), axis_sizes=(2, 2), extent=space / 2,
        halo_width=2.0, halo_capacity=96, migrate_capacity=48, depth=space,
        halo_codec="int16",
    )
    rng = np.random.default_rng(11)
    pos = rng.uniform(1.0, space - 1.0, (200, 3)).astype(np.float32)
    kinds = rng.integers(0, 2, 200)

    def build():
        return (
            Simulation(space=(0.0, space), cell_size=2.0, boundary="open",
                       dt=0.05, max_per_cell=32, seed=3, sort_frequency=4,
                       capacity=256)
            .add_agents(position=pos, diameter=1.6, kind=kinds)
            .mechanics(ForceParams())
            .observe_kinds("counts", n_kinds=2)
        ).distribute(mesh, dcfg)

    straight_final, straight_obs = build().run(12)

    class Die(Exception):
        pass

    def killer(state):
        if int(np.asarray(state.step).ravel()[0]) >= 6:
            raise Die

    d = tempfile.mkdtemp(prefix="dist_resume_")
    try:
        try:
            build().run(12, checkpoint_dir=d, checkpoint_every=3,
                        on_chunk=killer)
            raise AssertionError("killer never fired")
        except Die:
            pass
        resumed_final, resumed_obs = build().resume(d)
        np.testing.assert_array_equal(
            np.asarray(straight_obs["counts"]),
            np.asarray(resumed_obs["counts"]),
        )
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)),
            straight_final, resumed_final,
        )
    finally:
        shutil.rmtree(d, ignore_errors=True)
    print("distributed facade resume bit-exact OK")


def scenario_elastic_regrow():
    """Distributed elastic regrowth: an undersized per-device pool saturates
    under cell division; run_elastic_distributed restores the pre-chunk
    checkpoint into grown pools (+ scaled halo/migrate buffers) and replays
    to completion with zero drops, deterministically."""
    import shutil
    import tempfile

    from repro.core import Simulation
    from repro.core.behaviors import cell_division
    from repro.launch import elastic

    space = 32.0
    mesh = _mesh((2, 2), ("data", "model"))
    dcfg = DomainConfig(
        mesh_axes=("data", "model"), axis_sizes=(2, 2), extent=space / 2,
        halo_width=3.0, halo_capacity=64, migrate_capacity=32, depth=space,
        halo_codec="none",
    )
    rng = np.random.default_rng(5)
    pos = rng.uniform(3.0, space - 3.0, (48, 3)).astype(np.float32)

    def build():
        return (
            Simulation(space=(0.0, space), cell_size=3.0, boundary="open",
                       dt=1.0, max_per_cell=32, seed=2, capacity=256)
            .add_agents(position=pos, diameter=2.0)
            .use(cell_division(0.5))
            .observe("pop", lambda s: s.pool.alive.sum().astype(jnp.int32))
        )

    dirs = [tempfile.mkdtemp(prefix="dist_regrow_") for _ in range(2)]
    try:
        runs = [
            elastic.run_elastic_distributed(
                build(), mesh, dcfg, 4, d, checkpoint_every=2,
                capacity=32, max_regrows=4,
            )
            for d in dirs
        ]
        (f1, o1, g1), (f2, o2, g2) = runs
        assert g1 >= 1, f"expected at least one regrow, got {g1}"
        assert f1.pool.position.shape[1] > 32
        assert int(np.asarray(f1.pool.overflow).sum()) == 0
        assert int(np.asarray(f1.health.pool_overflow).sum()) == 0
        # Zero drops: final global population matches the recorded series.
        assert int(np.asarray(o1["pop"])[-1]) == int(
            np.asarray(f1.pool.alive).sum())
        assert g2 == g1
        np.testing.assert_array_equal(np.asarray(o1["pop"]),
                                      np.asarray(o2["pop"]))
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)),
            f1, f2,
        )
    finally:
        for d in dirs:
            shutil.rmtree(d, ignore_errors=True)
    print(f"distributed elastic regrowth OK (regrows={g1}, "
          f"final pop={int(np.asarray(o1['pop'])[-1])})")


def _overlap_setup(halo_capacity=96):
    """2×2 mesh with clusters straddling device faces and corners: real
    ghosts, real migration traffic — the overlap schedule's hardest diet."""
    extent, space = 16.0, 32.0
    mesh = _mesh((2, 2), ("data", "model"))
    dcfg = DomainConfig(
        mesh_axes=("data", "model"),
        axis_sizes=(2, 2),
        extent=extent,
        halo_width=2.0,
        halo_capacity=halo_capacity,
        migrate_capacity=48,
        depth=space,
        halo_codec="int16",
    )
    spec = dcfg.grid_spec(box_size=2.0, max_per_cell=32)
    ecfg = EngineConfig(
        spec=spec, behaviors=(), force_params=ForceParams(), dt=0.05,
        min_bound=0.0, max_bound=space, boundary="open", sort_frequency=4,
    )
    rng = np.random.default_rng(21)
    pos = rng.uniform(1.0, space - 1.0, (300, 3))
    # Dense blobs on the device faces and the 4-corner junction: every step
    # exchanges ghosts and pushes agents across boundaries (migration).
    blobs = [
        rng.uniform([15.0, 1.0, 4.0], [17.0, 31.0, 12.0], (40, 3)),
        rng.uniform([1.0, 15.0, 4.0], [31.0, 17.0, 12.0], (40, 3)),
        rng.uniform([15.2, 15.2, 4.0], [16.8, 16.8, 12.0], (20, 3)),
    ]
    pos = np.concatenate([pos] + blobs).astype(np.float32)
    return mesh, dcfg, ecfg, pos, pos.shape[0]


def _run_pair(mesh, dcfg, ecfg, pos, n_steps, capacity=256):
    """Run serial vs overlapped schedules from one initial state; return
    both final DistStates."""
    state0 = init_dist_state(dcfg, capacity=capacity, positions=pos,
                             diameter=1.6)
    finals = {}
    for name, d in (
        ("serial", dcfg),
        ("overlap", dataclasses.replace(dcfg, overlap_halo=True)),
    ):
        step = make_distributed_step(mesh, d, ecfg)
        s = state0
        for _ in range(n_steps):
            s = step(s)
        finals[name] = s
    return finals["serial"], finals["overlap"]


def _assert_states_equal(a, b, label):
    leaves_a, treedef_a = jax.tree.flatten(a)
    leaves_b, treedef_b = jax.tree.flatten(b)
    assert treedef_a == treedef_b, label
    paths = jax.tree_util.tree_flatten_with_path(a)[0]
    for (path, x), y in zip(paths, leaves_b):
        assert np.array_equal(np.asarray(x), np.asarray(y)), (
            f"{label}: {jax.tree_util.keystr(path)} diverged"
        )


def scenario_overlap_parity():
    """ISSUE 10 tentpole guard: the overlapped schedule (interior force
    concurrent with the halo collective, shell force after) must be
    BIT-EXACT against the serial schedule — full DistState, every variant:
    dense, fused + morton tiling, and a halo-overflow run where both
    schedules must drop the same ghosts."""
    # (a) dense path, steady ghost + migration traffic.
    mesh, dcfg, ecfg, pos, n = _overlap_setup()
    n_steps = 12
    serial, overlap = _run_pair(mesh, dcfg, ecfg, pos, n_steps)
    assert int(np.asarray(serial.pool.alive).sum()) == n
    _assert_states_equal(serial, overlap, "dense")
    print("overlap dense bit-exact OK")

    # (b) fused cell-list path with Z-order window tiles: the interior pass
    # runs pool-only sources (morton window engages), the shell pass runs
    # ghost-extended sources (linear order) — still bit-exact vs serial.
    ecfg_m = dataclasses.replace(
        ecfg, force_impl="fused", tile_order="morton")
    serial_m, overlap_m = _run_pair(mesh, dcfg, ecfg_m, pos, n_steps)
    _assert_states_equal(serial_m, overlap_m, "fused+morton")
    print("overlap fused+morton bit-exact OK")

    # (c) undersized halo capacity: the exchange truncates — serial and
    # overlapped schedules must truncate identically (overflow counters
    # fire, trajectories stay bit-exact).
    mesh, dcfg_s, ecfg, pos, n = _overlap_setup(halo_capacity=8)
    serial_o, overlap_o = _run_pair(mesh, dcfg_s, ecfg, pos, 6)
    assert int(np.asarray(serial_o.halo_overflow).sum()) > 0, \
        "overflow variant never overflowed — weaken halo_capacity further"
    _assert_states_equal(serial_o, overlap_o, "halo-overflow")
    print("overlap halo-overflow bit-exact OK")

    # Schedule shape: interior force is anchored before the exchange's
    # consumer, shell force after.
    from repro.core.distributed import distributed_scheduler

    names = [
        op.name
        for op in distributed_scheduler(
            dataclasses.replace(dcfg, overlap_halo=True), ecfg
        ).ordered_ops()
    ]
    assert names.index("migrate") < names.index("interior_env_build") \
        < names.index("halo_exchange") < names.index("env_build"), names
    assert names.index("interior_forces") < names.index("shell_forces"), names
    assert "forces" not in names, names
    print(f"overlap op sequence: {names}")
    print("overlap parity OK")


def scenario_overlap_smoke8():
    """CI smoke tier: serial vs overlapped on the full 8-device (4×2) mesh,
    asserting trajectory hash equality."""
    import hashlib

    mesh, dcfg, ecfg, pos, n = _force_only_setup("int16")
    n_steps = 10
    serial, overlap = _run_pair(mesh, dcfg, ecfg, pos, n_steps, capacity=192)

    def digest(state):
        h = hashlib.sha256()
        for leaf in jax.tree.leaves(state):
            h.update(np.asarray(leaf).tobytes())
        return h.hexdigest()

    hs, ho = digest(serial), digest(overlap)
    print(f"serial  state hash: {hs}")
    print(f"overlap state hash: {ho}")
    assert hs == ho, "overlapped schedule diverged from serial on 8 devices"
    assert int(np.asarray(serial.pool.alive).sum()) == n
    print("overlap smoke8 OK")


def scenario_diffusion_edge_parity():
    """ISSUE 10 satellite: distributed_diffuse used to torus-wrap the
    decomposed faces unconditionally.  With a non-toroidal boundary the
    wrap is now masked at mesh-edge devices, so a distributed diffusion run
    must reproduce the single-node zero-outside field — including the
    domain edges, where the old wrap leaked mass from the opposite face."""
    from repro.core import Simulation

    space, res = 32.0, 16
    mesh = _mesh((2, 2), ("data", "model"))
    dcfg = DomainConfig(
        mesh_axes=("data", "model"), axis_sizes=(2, 2), extent=space / 2,
        halo_width=2.0, halo_capacity=32, migrate_capacity=16, depth=space,
    )
    rng = np.random.default_rng(4)
    field = rng.uniform(0.0, 1.0, (res, res, res)).astype(np.float32)
    pos = rng.uniform(4.0, space - 4.0, (8, 3)).astype(np.float32)
    n_steps = 10

    def build(boundary):
        return (
            Simulation(space=(0.0, space), cell_size=2.0, boundary=boundary,
                       dt=0.05, max_per_cell=32, capacity=16)
            .add_agents(position=pos, diameter=1.6)
            .add_substance("s", diffusion=1.0, resolution=res,
                           concentration=field)
        )

    single, _ = build("open").run_jit(n_steps)
    ref = np.asarray(single.grids["s"].concentration)

    def reassemble(stacked):
        out = np.zeros((res, res, res), np.float32)
        h = res // 2
        for dev in range(4):
            cx, cy = divmod(dev, 2)
            out[cx * h:(cx + 1) * h, cy * h:(cy + 1) * h] = stacked[dev]
        return out

    dist_state, _ = build("open").distribute(mesh, dcfg).run(n_steps)
    got = reassemble(np.asarray(dist_state.grids["s"].concentration))
    err = np.abs(got - ref).max()
    print(f"open-boundary max |dist - single| = {err:.2e}")
    np.testing.assert_allclose(got, ref, rtol=0.0, atol=1e-6)

    # Positive control: a toroidal distributed run DOES wrap, so its edge
    # voxels must differ from the zero-outside reference (proves the mask
    # above is load-bearing, not vacuous).
    tor_state, _ = build("toroidal").distribute(mesh, dcfg).run(n_steps)
    tor = reassemble(np.asarray(tor_state.grids["s"].concentration))
    edge_delta = np.abs(tor[0] - ref[0]).max()
    assert edge_delta > 1e-4, (
        f"toroidal control indistinguishable from open ({edge_delta:.2e}) — "
        "the edge-parity assertion is not exercising the wrap path"
    )
    print(f"toroidal control edge delta = {edge_delta:.2e}")
    print("diffusion edge parity OK")


def scenario_diffusion_uneven_parity():
    """ISSUE 10 satellite: uneven substance resolution (33 on a 2×2 mesh)
    distributes via ghost-voxel padding; the reassembled valid voxels must
    match the single-node field after real diffusion steps."""
    from repro.core import Simulation

    space, res = 32.0, 33
    mesh = _mesh((2, 2), ("data", "model"))
    dcfg = DomainConfig(
        mesh_axes=("data", "model"), axis_sizes=(2, 2), extent=space / 2,
        halo_width=2.0, halo_capacity=32, migrate_capacity=16, depth=space,
    )
    rng = np.random.default_rng(5)
    field = rng.uniform(0.0, 1.0, (res, res, res)).astype(np.float32)
    pos = rng.uniform(4.0, space - 4.0, (8, 3)).astype(np.float32)
    n_steps = 10

    def build():
        return (
            Simulation(space=(0.0, space), cell_size=2.0, boundary="open",
                       dt=0.05, max_per_cell=32, capacity=16)
            .add_agents(position=pos, diameter=1.6)
            .add_substance("s", diffusion=1.0, resolution=res,
                           concentration=field)
        )

    single, _ = build().run_jit(n_steps)
    ref = np.asarray(single.grids["s"].concentration)

    dist_state, _ = build().distribute(mesh, dcfg).run(n_steps)
    stacked = np.asarray(dist_state.grids["s"].concentration)  # (4,17,17,33)
    n_valid = np.asarray(dist_state.grids["s"].n_valid)        # (4,3)
    per = -(-res // 2)
    got = np.zeros((res, res, res), np.float32)
    for dev in range(4):
        cx, cy = divmod(dev, 2)
        nv = n_valid[dev]
        lo = (cx * per, cy * per, 0)
        block = stacked[dev][: nv[0], : nv[1], : nv[2]]
        got[lo[0]:lo[0] + nv[0], lo[1]:lo[1] + nv[1], lo[2]:lo[2] + nv[2]] \
            = block
        # Padding must stay pinned at zero through the steps.
        assert (stacked[dev][nv[0]:] == 0).all(), dev
        assert (stacked[dev][:, nv[1]:] == 0).all(), dev
    err = np.abs(got - ref).max()
    print(f"uneven split max |dist - single| after {n_steps} steps = {err:.2e}")
    np.testing.assert_allclose(got, ref, rtol=0.0, atol=1e-6)
    print("diffusion uneven parity OK")


def scenario_codec_full_depth():
    """A cube on a 2x2 mesh: the non-decomposed depth (48) is larger than
    the halo-extended decomposed extent (24 + 2·2).  The int16 halo codec
    must span the depth too — ghosts above z = 28 used to saturate there,
    piling into one cell layer and feeding wrong pair forces."""
    from repro.core import Simulation

    space = 48.0
    mesh = _mesh((2, 2), ("data", "model"))
    rng = np.random.default_rng(11)
    pos = rng.uniform(4.0, space - 4.0, (4000, 3)).astype(np.float32)
    finals = {}
    for codec in ("int16", "none"):
        dcfg = DomainConfig(
            mesh_axes=("data", "model"), axis_sizes=(2, 2), extent=space / 2,
            halo_width=2.0, halo_capacity=256, migrate_capacity=64,
            depth=space, halo_codec=codec,
        )
        sim = (
            Simulation(space=(0.0, space), cell_size=2.0, boundary="closed",
                       dt=0.05, max_per_cell=32)
            .add_agents(position=pos, diameter=1.6)
            .mechanics(ForceParams())
        )
        st, _ = sim.distribute(mesh, dcfg, capacity=1500).run(2)
        assert int(np.asarray(st.health.cell_overflow_steps).sum()) == 0
        # Per-dim quantum: x/y span the halo-extended extent, z the depth.
        np.testing.assert_allclose(
            np.asarray(st.codec.scale)[0],
            np.float32([28.0, 28.0, space]) / np.float32(32767.0),
        )
        # Migration is decided before the forces, so both codecs share the
        # slot layout and per-slot positions compare directly.
        finals[codec] = np.asarray(st.pool.position)[np.asarray(st.pool.alive)]
    assert finals["int16"].shape == finals["none"].shape
    err = float(np.abs(finals["int16"] - finals["none"]).max())
    print(f"int16 vs f32 halo wire, max position error {err:.2e}")
    assert err < 2e-3, err
    print("codec full depth OK")


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    table = {
        "conservation": scenario_conservation,
        "parity": lambda: scenario_parity_simple("int16"),
        "parity_none": lambda: scenario_parity_simple("none"),
        "codec": scenario_codec_reduction,
        "multipod": scenario_multipod,
        "fused_parity": scenario_fused_parity,
        "fused_dead": scenario_fused_dead_agents,
        "fused_overflow": scenario_fused_overflow_fallback,
        "telemetry": scenario_telemetry,
        "packing_no_sort": scenario_packing_no_sort,
        "lazy_candidates": scenario_lazy_candidates,
        "facade_parity": scenario_facade_parity,
        "scheduler_parity": scenario_scheduler_parity,
        "static_flags": scenario_static_flags_distributed,
        "bounds": scenario_bounds_honored,
        "health_cell_overflow": scenario_health_cell_overflow,
        "facade_resume": scenario_facade_resume,
        "elastic_regrow": scenario_elastic_regrow,
        "overlap_parity": scenario_overlap_parity,
        "overlap_smoke8": scenario_overlap_smoke8,
        "diffusion_edge_parity": scenario_diffusion_edge_parity,
        "diffusion_uneven_parity": scenario_diffusion_uneven_parity,
        "codec_full_depth": scenario_codec_full_depth,
    }
    if which == "all":
        for name, fn in table.items():
            print(f"--- {name}")
            fn()
    else:
        table[which]()
    print("SCENARIOS PASSED")
