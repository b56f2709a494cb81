"""TeraAgent distributed-engine tests (Ch. 6).

The engine needs multiple devices; each test spawns a subprocess with
``--xla_force_host_platform_device_count=8`` (the main pytest process keeps
the real single-device view, per the dry-run isolation rule).
"""

import os
import subprocess
import sys

import pytest

_SCRIPT = os.path.join(os.path.dirname(__file__), "dist_scenarios.py")


def _run(scenario: str, timeout: int = 540) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    proc = subprocess.run(
        [sys.executable, _SCRIPT, scenario],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
    )
    assert proc.returncode == 0, f"scenario {scenario} failed:\n{proc.stdout}\n{proc.stderr}"
    return proc.stdout


@pytest.mark.subprocess
def test_agent_conservation():
    out = _run("conservation")
    assert "conservation OK" in out


@pytest.mark.subprocess
@pytest.mark.slow
def test_physics_parity_with_single_node():
    """The distributed engine is the *same simulation* split over devices:
    20 relaxation steps must land every agent where the single-node engine
    puts it (§6.3.3 correctness verification)."""
    out = _run("parity")
    assert "parity OK" in out


@pytest.mark.subprocess
@pytest.mark.slow
def test_delta_codec_physics_bound():
    """§6.2.3: quantized halo deltas change physics only within the bound."""
    out = _run("codec")
    assert "codec reduction OK" in out


@pytest.mark.subprocess
def test_multipod_3d_decomposition():
    out = _run("multipod")
    assert "multipod OK" in out


@pytest.mark.subprocess
@pytest.mark.slow
def test_fused_force_parity_distributed():
    """DESIGN.md §4 distributed adoption: the fused cell-list force pass over
    the ghost-extended grid (corner-halo agents included) must match both the
    dense distributed path and the single-node fused engine."""
    out = _run("fused_parity")
    assert "fused parity OK" in out


@pytest.mark.subprocess
def test_fused_dead_agents_distributed():
    out = _run("fused_dead")
    assert "fused dead agents OK" in out


@pytest.mark.subprocess
def test_fused_overflow_falls_back_distributed():
    """Halo-extended cell-list overflow → lax.cond dense fallback, exactly."""
    out = _run("fused_overflow")
    assert "fused overflow fallback OK" in out


@pytest.mark.subprocess
def test_halo_wire_telemetry():
    """DistState carries exact cumulative payload/baseline wire bytes."""
    out = _run("telemetry")
    assert "telemetry OK" in out


@pytest.mark.subprocess
def test_packing_is_sort_free():
    """migrate/halo_exchange packing lowers with zero sort ops."""
    out = _run("packing_no_sort")
    assert "packing sort-free OK" in out


@pytest.mark.subprocess
def test_distributed_candidates_lazy():
    """Fused distributed step never materializes the (C, 27M) tensor."""
    out = _run("lazy_candidates")
    assert "lazy candidates OK" in out


@pytest.mark.subprocess
def test_facade_distributed_parity():
    """DESIGN.md §6: Simulation.distribute compiles onto the explicit
    distributed wiring bit-exactly (2×2 mesh), incl. domain-split
    substances."""
    out = _run("facade_parity")
    assert "facade parity OK" in out


@pytest.mark.subprocess
def test_scheduler_op_sequence_parity():
    """DESIGN.md §5: the distributed schedule is the single-node schedule
    op-for-op, with distribution composed as inserted/replaced ops."""
    out = _run("scheduler_parity")
    assert "scheduler parity OK" in out


@pytest.mark.subprocess
def test_distributed_runs_static_flag_detection():
    """Regression: the duplicated distributed pipeline dropped §5.5 static
    detection; through the shared scheduler it runs by construction."""
    out = _run("static_flags")
    assert "distributed static flags OK" in out


@pytest.mark.subprocess
def test_health_attributes_cell_overflow_to_device():
    """DESIGN.md §7: an injected over-full cell flips ``index.overflowed``
    only on the device that owns it, the dense fallback stays bit-exact,
    and the health op folds the flag into per-device counters."""
    out = _run("health_cell_overflow")
    assert "distributed cell-overflow health OK" in out


@pytest.mark.subprocess
@pytest.mark.slow
def test_facade_resume_bit_exact():
    """Kill-and-resume through Simulation.distribute: k + kill + resume + k
    reproduces the uninterrupted 2k-step run bit-for-bit — state and the
    full observable series."""
    out = _run("facade_resume")
    assert "distributed facade resume bit-exact OK" in out


@pytest.mark.subprocess
@pytest.mark.slow
def test_elastic_regrowth_distributed():
    """Overflow-driven regrowth under the distributed engine: saturated
    per-device pools grow, no agents are dropped, and the run is
    deterministic."""
    out = _run("elastic_regrow")
    assert "distributed elastic regrowth OK" in out


@pytest.mark.subprocess
@pytest.mark.slow
def test_overlap_schedule_bit_exact():
    """ISSUE 10 tentpole: the overlapped halo schedule (interior forces
    concurrent with the collective, boundary-shell forces after) is
    bit-exact vs the serial schedule — dense, fused+morton, and a
    halo-overflow run."""
    out = _run("overlap_parity")
    assert "overlap parity OK" in out


@pytest.mark.subprocess
def test_overlap_smoke_8_devices():
    """Serial vs overlapped state-hash equality on the full 8-device mesh
    (the same check scripts/ci.sh runs as its overlap tier)."""
    out = _run("overlap_smoke8")
    assert "overlap smoke8 OK" in out


@pytest.mark.subprocess
def test_distributed_diffusion_edge_parity():
    """ISSUE 10 bugfix: non-toroidal boundaries must not torus-wrap the
    decomposed faces of distributed diffusion."""
    out = _run("diffusion_edge_parity")
    assert "diffusion edge parity OK" in out


@pytest.mark.subprocess
def test_distributed_diffusion_uneven_resolution():
    """ISSUE 10 bugfix: uneven substance splits run via ghost-voxel padding
    and match the single-node field."""
    out = _run("diffusion_uneven_parity")
    assert "diffusion uneven parity OK" in out


@pytest.mark.subprocess
def test_halo_codec_spans_full_depth():
    """Regression: the int16 halo codec's scale spanned only the decomposed
    extent, so on a cube split 2x2 ghost z saturated at extent + 2·halo."""
    out = _run("codec_full_depth")
    assert "codec full depth OK" in out


@pytest.mark.subprocess
def test_distributed_honors_engine_bounds():
    """Regression: the distributed step ignored EngineConfig.min_bound/
    max_bound/boundary for non-decomposed dims (hardcoded closed [0, depth])."""
    out = _run("bounds")
    assert "bounds honored OK" in out


# ---------------------------------------------------------------------------
# In-process unit tests (no devices needed): the sort-free packing primitives.
# ---------------------------------------------------------------------------


def test_codec_span_is_per_dim():
    """The halo codec spans the halo-extended extent on decomposed dims and
    the depth on the rest, so a deep domain widens only z's quantum."""
    from repro.core.distributed import DomainConfig

    two = DomainConfig(
        mesh_axes=("data", "model"), axis_sizes=(2, 2), extent=24.0,
        halo_width=2.0, halo_capacity=8, migrate_capacity=8, depth=48.0,
    )
    assert two.codec_span == (28.0, 28.0, 48.0)
    three = DomainConfig(
        mesh_axes=("data", "model", "pod"), axis_sizes=(2, 2, 2),
        extent=24.0, halo_width=2.0, halo_capacity=8, migrate_capacity=8,
    )
    assert three.codec_span == (28.0, 28.0, 28.0)


def test_select_matches_stable_argsort_reference():
    """_select's cumsum-rank compaction must reproduce the stable-argsort
    semantics it replaced: selected ids in ascending index order, exact
    valid prefix, exact overflow count."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.distributed import _select

    rng = np.random.default_rng(0)
    for case in range(20):
        c = int(rng.integers(1, 200))
        capacity = int(rng.integers(1, 32))
        mask = rng.random(c) < rng.random()
        ids, valid, overflow = _select(jnp.asarray(mask), capacity)
        ids, valid = np.asarray(ids), np.asarray(valid)
        expected = np.nonzero(mask)[0]
        n = len(expected)
        k = min(n, capacity)
        np.testing.assert_array_equal(ids[:k], expected[:k], err_msg=str(case))
        np.testing.assert_array_equal(valid, np.arange(capacity) < k)
        assert int(overflow) == max(n - capacity, 0)


def test_free_slot_table_matches_sort_reference():
    import jax.numpy as jnp
    import numpy as np

    from repro.core.agents import free_slot_table

    rng = np.random.default_rng(1)
    for _ in range(10):
        c = int(rng.integers(1, 150))
        alive = rng.random(c) < 0.6
        got = np.asarray(free_slot_table(jnp.asarray(alive)))
        ref = np.sort(np.where(~alive, np.arange(c), c))
        np.testing.assert_array_equal(got, ref)


def test_interior_shell_masks_partition_live_cells():
    """ISSUE 10: interior/shell membership from cell coordinates must
    PARTITION the live rows exactly — disjoint, union == alive, dead rows
    in neither — with interior conservatively clear of the decomposed
    faces (any live row within one cell of a face is shell) and rows deep
    inside the owned band interior."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.distributed import DomainConfig, interior_shell_masks

    extent, box = 16.0, 2.0
    dcfg = DomainConfig(
        mesh_axes=("data", "model"), axis_sizes=(2, 2), extent=extent,
        halo_width=2.0, halo_capacity=32, migrate_capacity=16, depth=32.0,
    )
    spec = dcfg.grid_spec(box_size=box, max_per_cell=32)

    rng = np.random.default_rng(6)
    n = 512
    # Spread over the halo-extended band: owned [0, 16) plus ghost margins
    # (coords < 0 and ≥ extent model halo rows and migrate leftovers).
    pos = rng.uniform(-2.0, extent + 2.0, (n, 3)).astype(np.float32)
    pos[:, 2] = rng.uniform(0.0, 32.0, n)  # z is not decomposed
    alive = rng.random(n) < 0.8

    interior, shell = interior_shell_masks(
        dcfg, spec, jnp.asarray(pos), jnp.asarray(alive))
    interior, shell = np.asarray(interior), np.asarray(shell)

    assert not (interior & shell).any(), "masks overlap"
    np.testing.assert_array_equal(interior | shell, alive)
    assert not (interior & ~alive).any() and not (shell & ~alive).any()

    # Necessary: interior rows sit at least one full cell from both faces
    # of every decomposed dim (x and y here; z unconstrained).
    for d in range(dcfg.n_decomposed):
        c = pos[interior, d]
        assert (c >= box).all() and (c <= extent - box).all(), d
    # Sufficient (conservative): rows ≥ 2 cells clear of every decomposed
    # face are interior.
    deep = alive.copy()
    for d in range(dcfg.n_decomposed):
        deep &= (pos[:, d] >= 2 * box) & (pos[:, d] < extent - 2 * box)
    assert deep.any(), "test layout produced no deep-interior rows"
    assert interior[deep].all(), "deep-interior live rows not marked interior"
    # Ghost-band rows (outside the owned band) are never interior.
    outside = alive & (
        (pos[:, : dcfg.n_decomposed] < 0).any(axis=1)
        | (pos[:, : dcfg.n_decomposed] >= extent).any(axis=1)
    )
    assert shell[outside].all(), "ghost-band rows leaked into interior"
