"""Scheduler subsystem tests (core/schedule.py, DESIGN.md §5).

Covers the Algorithm-8-as-data contract: frequency semantics (0 disables,
mod-mask vs lax.cond gating bit-exact, ⌈n/k⌉ firings under lax.scan), phase
ordering, and the insert/replace/remove composition API the few-lines-of-
code modularity claim rests on.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    EngineConfig,
    ForceParams,
    Operation,
    Scheduler,
    init_state,
    make_grid,
    make_pool,
    random_movement,
    run_jit,
    spec_for_space,
)


def _setup(n=24, space=30.0, grids=False, **cfg):
    rng = np.random.default_rng(0)
    pos = jnp.asarray(rng.uniform(2.0, space - 2.0, (n, 3)), jnp.float32)
    pool = make_pool(n, pos, diameter=2.0,
                     attrs={"fires": jnp.zeros((n,), jnp.float32)})
    config = EngineConfig(
        spec=spec_for_space(0.0, space, 5.0, max_per_cell=32),
        force_params=ForceParams(),
        dt=0.1,
        min_bound=0.0,
        max_bound=space,
        boundary="closed",
        **cfg,
    )
    g = {"sub": make_grid(0.0, space, 8, diffusion_coefficient=2.0)} if grids else None
    return config, init_state(pool, g, seed=1)


def _count_op(frequency, gate="cond"):
    def fn(ctx, state):
        pool = state.pool
        return dataclasses.replace(
            state, pool=pool.set_attr("fires", pool.get("fires") + 1.0)
        )
    return Operation("census", fn, phase="post", frequency=frequency, gate=gate)


# ------------------------------------------------------------ default schedule

def test_default_pipeline_order():
    config, _ = _setup()
    names = [op.name for op in Scheduler.default(config).ordered_ops()]
    assert names == ["sort", "env_build", "behaviors", "forces", "boundary",
                     "static_flags", "diffusion", "age", "health"]


def test_force_free_config_omits_force_ops():
    config, _ = _setup()
    config = dataclasses.replace(config, force_params=None,
                                 behaviors=(random_movement(0.5),))
    names = Scheduler.default(config).op_names()
    assert "forces" not in names and "static_flags" not in names


def _frozen_reference_step(config, state):
    """The pre-scheduler inline simulation_step, frozen as the semantic
    reference the schedule must keep reproducing bit-for-bit
    (simulation_step itself now delegates to the scheduler, so comparing
    against it would be tautological).  One post-freeze amendment: the
    force pass adopts the scheduler's rounding contract — the ``lax.cond``
    fusion fence plus ``seal`` on the force and on the ``force·dt``
    product (see ``schedule.force_pass``/``apply_force``).  The fence is
    semantically a no-op but rounding-visible (it fixes which of several
    IEEE-legal evaluations XLA picks), so a reference without it would pin
    the *old* rounding, not the old semantics."""
    from repro.core.behaviors import StepContext
    from repro.core.delta import seal
    from repro.core.engine import SimulationState
    from repro.core.forces import mechanical_forces, update_static_flags_celllist
    from repro.core.grid import build_index, sort_agents
    from repro.core.neighbors import NeighborContext
    from repro.core.schedule import apply_boundary
    from repro.core import diffusion as dgrid

    pool = state.pool
    if config.sort_frequency > 0:
        do_sort = (state.step % config.sort_frequency) == 0
        pool = jax.lax.cond(
            do_sort, lambda p: sort_agents(config.spec, p), lambda p: p, pool
        )
    index = build_index(config.spec, pool)
    neighbors = NeighborContext.for_pool(config.spec, index, pool)
    ctx = StepContext(
        rng=jax.random.fold_in(state.rng, state.step),
        grids=dict(state.grids), neighbors=neighbors,
        dt=jnp.float32(config.dt), step=state.step,
        min_bound=config.min_bound, max_bound=config.max_bound,
    )
    pre_behavior_pos = pool.position
    for behavior in config.behaviors:
        ctx, pool = behavior(ctx, pool)
    if config.force_params is not None:
        def _run(_):
            return mechanical_forces(
                config.spec, index, pool, config.force_params,
                active_capacity=config.active_capacity, impl=config.force_impl,
                neighbors=neighbors,
                fused_fallback=config.fused_overflow_fallback,
                tile=config.force_tile,
            )

        def _zero(_):
            return jnp.zeros((pool.capacity, 3), jnp.float32)

        force = seal(jax.lax.cond(jnp.any(pool.alive), _run, _zero, None))
        pool = pool.replace(position=pool.position + seal(force * config.dt))
    pool = pool.replace(position=apply_boundary(config, pool.position))
    if config.force_params is not None:
        displacement = pool.position - pre_behavior_pos
        pool = update_static_flags_celllist(
            config.spec, index, pool, displacement, config.force_params,
            query_position=neighbors.query_position,
        )
    grids = dict(ctx.grids)
    if grids and config.diffusion_frequency > 0:
        do_diffuse = (state.step % config.diffusion_frequency) == 0
        for name, g in grids.items():
            grids[name] = jax.lax.cond(
                do_diffuse,
                lambda gg: dgrid.diffuse(
                    gg, config.dt * config.diffusion_frequency,
                    impl=config.diffusion_impl,
                ),
                lambda gg: gg, g,
            )
    pool = pool.replace(age=pool.age + jnp.where(pool.alive, config.dt, 0.0))
    # The frozen reference predates the health op — carry the report through
    # unchanged; the bitwise comparison below masks it out.
    return SimulationState(pool=pool, grids=grids, rng=state.rng,
                           step=state.step + 1, health=state.health)


def test_step_matches_frozen_reference_bitwise():
    """The scheduler pipeline reproduces the pre-refactor inline step
    bit-for-bit, across several steps (sort and diffusion frequencies both
    exercise their gates)."""
    config, state = _setup(grids=True, sort_frequency=2, diffusion_frequency=3,
                           behaviors=(random_movement(0.4),))
    a, b = state, state
    for _ in range(4):
        a = jax.jit(Scheduler.default(config).step)(a)
        b = jax.jit(lambda s: _frozen_reference_step(config, s))(b)
    # health is the one post-refactor addition the reference doesn't model —
    # compare everything else bitwise.
    a_cmp = dataclasses.replace(a, health=b.health)
    jax.tree.map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x), np.asarray(y)),
        a_cmp, b,
    )
    assert int(a.step) == 4


# -------------------------------------------------------- frequency semantics

def test_frequency_zero_disables_op():
    """sort_frequency / diffusion_frequency = 0 statically disable the ops:
    the grid concentration never changes and agent order is never permuted."""
    config, state = _setup(grids=True, sort_frequency=0, diffusion_frequency=0)
    state = dataclasses.replace(
        state,
        grids={"sub": dataclasses.replace(
            state.grids["sub"],
            concentration=state.grids["sub"].concentration.at[4, 4, 4].set(7.0),
        )},
    )
    final, _ = run_jit(config, state, 5)
    np.testing.assert_array_equal(
        np.asarray(final.grids["sub"].concentration),
        np.asarray(state.grids["sub"].concentration),
    )


def test_frequency_zero_custom_op_never_fires():
    config, state = _setup()
    sched = Scheduler.default(config).append(_count_op(frequency=0))
    final, _ = run_jit(config, state, 6, scheduler=sched)
    assert float(final.pool.get("fires")[0]) == 0.0


@pytest.mark.parametrize("n_steps,k", [(10, 3), (7, 2), (5, 5), (4, 1)])
def test_custom_op_fires_ceil_n_over_k_times(n_steps, k):
    """A frequency-k op fires on step % k == 0 → exactly ⌈n/k⌉ times over an
    n-step lax.scan from step 0."""
    config, state = _setup()
    sched = Scheduler.default(config).append(_count_op(frequency=k))
    final, _ = run_jit(config, state, n_steps, scheduler=sched)
    assert float(final.pool.get("fires")[0]) == -(-n_steps // k)


def test_cond_and_mask_gating_bit_exact():
    """The two frequency lowerings (lax.cond skip vs predicated where-select)
    must produce bit-identical trajectories."""
    config, state = _setup(grids=True)

    def shove(ctx, state):
        pool = state.pool
        return dataclasses.replace(
            state,
            pool=pool.replace(position=pool.position + jnp.float32(0.37)),
        )

    finals = {}
    for gate in ("cond", "mask"):
        op = Operation("shove", shove, phase="agent", frequency=3, gate=gate)
        sched = Scheduler.default(config).insert_before("forces", op)
        finals[gate], _ = run_jit(config, state, 8, scheduler=sched)
    jax.tree.map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x), np.asarray(y)),
        finals["cond"], finals["mask"],
    )
    # positive control: the op did fire (steps 0, 3, 6)
    assert not np.allclose(
        np.asarray(finals["cond"].pool.position), np.asarray(state.pool.position)
    )


def test_engine_frequency_gating_matches_mask_variant():
    """The engine's cond-gated sort op agrees bit-exactly with a mask-gated
    clone of the same op (frequency semantics are gate-independent)."""
    config, state = _setup(sort_frequency=2)
    base = Scheduler.default(config)
    masked = base.replace_op(
        "sort", dataclasses.replace(base.ops[0], gate="mask")
    )
    assert base.ops[0].name == "sort" and base.ops[0].gate == "cond"
    a, _ = run_jit(config, state, 6)
    b, _ = run_jit(config, state, 6, scheduler=masked)
    jax.tree.map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x), np.asarray(y)),
        a, b,
    )


# --------------------------------------------------------------- composition

def test_phase_partition_overrides_tuple_order():
    """An appended pre op runs before agent/post ops regardless of position."""
    config, _ = _setup()
    noop = Operation("late_pre", lambda ctx, s: s, phase="pre")
    names = [op.name for op in Scheduler.default(config).append(noop).ordered_ops()]
    assert names.index("late_pre") < names.index("behaviors")
    assert names.index("late_pre") > names.index("env_build")


def test_insert_replace_remove():
    config, _ = _setup()
    sched = Scheduler.default(config)
    op = _count_op(frequency=1)
    assert sched.insert_after("forces", op).op_names().index("census") == \
        sched.op_names().index("forces") + 1
    assert sched.insert_before("forces", op).op_names().index("census") == \
        sched.op_names().index("forces")
    replaced = sched.replace_op("age", Operation("age", lambda c, s: s, phase="post"))
    assert replaced.op_names() == sched.op_names()
    assert "age" not in sched.remove_op("age").op_names()


def test_unknown_and_duplicate_names_raise():
    config, _ = _setup()
    sched = Scheduler.default(config)
    with pytest.raises(KeyError):
        sched.insert_after("nope", _count_op(1))
    with pytest.raises(KeyError):
        sched.remove_op("nope")
    with pytest.raises(KeyError):
        sched.append(Operation("sort", lambda c, s: s, phase="pre"))


def test_operation_validation():
    with pytest.raises(ValueError):
        Operation("x", lambda c, s: s, phase="mid")
    with pytest.raises(ValueError):
        Operation("x", lambda c, s: s, gate="maybe")
    with pytest.raises(ValueError):
        Operation("x", lambda c, s: s, frequency=-1)


def test_custom_op_reads_op_context():
    """Custom ops see the per-step scratch (index/neighbors) standalone ops
    published — the few-lines-of-code extension surface."""
    config, state = _setup()
    seen = {}

    def probe(ctx, s):
        seen["has_index"] = ctx.index is not None
        seen["has_neighbors"] = ctx.neighbors is not None
        seen["config"] = ctx.config is config
        return s

    sched = Scheduler.default(config).insert_after(
        "behaviors", Operation("probe", probe, phase="agent")
    )
    sched.step(state)  # unjitted trace is enough
    assert seen == {"has_index": True, "has_neighbors": True, "config": True}
