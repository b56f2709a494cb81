"""The program's own trace spans (`repro.spans`).

Device scopes: every scheduler op is named in the compiled module's
``op_name`` metadata by ``schedule.run_op``, whatever its gate; the fused
force pass names its gather, kernel and scatter.  Host spans: a jitted run
writes ``read_step`` and ``launch`` per call and ``trace_schedule`` once per
trace, read here back from a ``jax.profiler`` trace.
"""

import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import Simulation, spans
from repro.core import (
    EngineConfig,
    ForceParams,
    Operation,
    Scheduler,
    init_state,
    make_grid,
    make_pool,
    random_movement,
    spec_for_space,
)
from repro.core.distributed import DomainConfig, distributed_scheduler
from repro.core.forces import mechanical_forces
from repro.core.grid import build_index
from repro.kernels.cell_force import ops as cf_ops


def scopes(compiled_text: str) -> set:
    """Every name-stack segment in the module's ``op_name`` metadata."""
    out = set()
    for path in re.findall(r'op_name="([^"]*)"', compiled_text):
        out.update(path.split("/"))
    return out


def _bump(name, phase, frequency, gate):
    def fn(ctx, state):
        pool = state.pool
        return dataclasses.replace(
            state, pool=pool.set_attr("dose", pool.get("dose")
                                      + pool.position[:, 0]))
    return Operation(name, fn, phase=phase, frequency=frequency, gate=gate)


def _config(n=32, space=30.0):
    rng = np.random.default_rng(0)
    pos = jnp.asarray(rng.uniform(2.0, space - 2.0, (n, 3)), jnp.float32)
    pool = make_pool(n, pos, diameter=2.0,
                     attrs={"dose": jnp.zeros((n,), jnp.float32)})
    config = EngineConfig(
        spec=spec_for_space(0.0, space, 5.0, max_per_cell=16),
        behaviors=(random_movement(0.5),),
        force_params=ForceParams(),
        dt=0.1, min_bound=0.0, max_bound=space, boundary="closed",
        sort_frequency=4, diffusion_frequency=2,
    )
    grids = {"sub": make_grid(0.0, space, 8, diffusion_coefficient=2.0)}
    return config, init_state(pool, grids, seed=1)


def test_compiled_step_names_every_op():
    """Cond-gated (``sort``, ``diffusion``), mask-gated and custom ops
    inserted with ``insert_after`` all carry their name."""
    config, state = _config()
    sched = (Scheduler.default(config)
             .insert_after("behaviors", _bump("masked", "agent", 2, "mask"))
             .insert_after("age", _bump("custom", "post", 1, "cond")))
    gates = {op.name: (op.frequency, op.gate) for op in sched.ops}
    assert gates["sort"] == (4, "cond") and gates["diffusion"] == (2, "cond")
    assert gates["masked"] == (2, "mask")
    text = jax.jit(sched.step).lower(state).compile().as_text()
    found = scopes(text)
    missing = [op.name for op in sched.ops if op.name not in found]
    assert not missing, missing


def test_compiled_cell_list_force_names_its_stages():
    rng = np.random.default_rng(1)
    dims, m, n = (4, 3, 2), 4, 40
    pos = jnp.asarray(rng.uniform(0.0, 10.0, (n, 3)), jnp.float32)
    rad = jnp.full((n,), 1.0, jnp.float32)
    slots = rng.permutation(np.prod(dims) * m)[:n]
    cell_list = np.full(np.prod(dims) * m, n, np.int32)
    cell_list[slots] = np.arange(n)
    cell_list = jnp.asarray(cell_list.reshape(-1, m))
    text = cf_ops.cell_list_force.lower(
        pos, rad, cell_list, jnp.asarray(slots, jnp.int32), dims
    ).compile().as_text()
    found = scopes(text)
    for stage in (spans.CELL_GATHER, spans.CELL_KERNEL, spans.CELL_SCATTER):
        assert stage in found, stage


def test_compiled_overflow_fallback_is_named():
    config, state = _config()
    index = build_index(config.spec, state.pool)
    text = jax.jit(
        lambda index, pool: mechanical_forces(
            config.spec, index, pool, config.force_params, impl="fused")
    ).lower(index, state.pool).compile().as_text()
    found = scopes(text)
    assert spans.DENSE_FALLBACK in found and spans.CELL_KERNEL in found


@pytest.mark.parametrize("overlap", [False, True])
def test_no_stage_name_is_an_op_name(overlap):
    config, _ = _config()
    dcfg = DomainConfig(mesh_axes=("x",), axis_sizes=(2,), extent=15.0,
                        depth=30.0, halo_width=2.5, halo_capacity=16,
                        migrate_capacity=16, overlap_halo=overlap)
    ops = set(Scheduler.default(config).op_names())
    ops |= set(distributed_scheduler(dcfg, config).op_names())
    assert {"forces", "migrate", "halo_exchange"} <= ops
    if overlap:
        assert {"interior_env_build", "interior_forces", "shell_forces"} <= ops
    names = spans.STAGES + spans.HOST_SPANS
    assert len(set(names)) == len(names)
    assert not ops & set(names)


def host_span_counts(trace_dir) -> dict:
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    counts = dict.fromkeys(spans.HOST_SPANS, 0)
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in counts:
                    counts[ev.name] += 1
    return counts


def _traced_chunks(built, trace_dir, chunks=2, steps=2):
    state = built.state
    jax.profiler.start_trace(str(trace_dir))
    try:
        for _ in range(chunks):
            state, _ = built.run_jit(steps, state=state)
        jax.block_until_ready(state)
    finally:
        jax.profiler.stop_trace()
    return host_span_counts(trace_dir)


def _model(**attrs):
    n = 48
    pos = np.random.default_rng(2).uniform(0.0, 40.0, (n, 3)).astype(
        np.float32)

    def dose(ctx, state):
        pool = state.pool
        return dataclasses.replace(
            state, pool=pool.set_attr("exposure", pool.get("exposure")
                                      + pool.position[:, 0]))

    return (Simulation(space=(0.0, 40.0), cell_size=10.0, seed=0)
            .add_agents(n, position=pos, diameter=5.0, **attrs)
            .op(dose, name="dose", phase="post"))


def test_same_shape_run_jit_calls_trace_once(tmp_path):
    built = _model(exposure=jnp.zeros(48, jnp.float32)).build()
    counts = _traced_chunks(built, tmp_path)
    assert counts == {spans.READ_STEP: 2, spans.LAUNCH: 2,
                      spans.TRACE_SCHEDULE: 1}


def test_scalar_attribute_is_strong_and_traces_once(tmp_path):
    """A scalar attribute broadcasts strongly typed, as the step's output
    is, so the second chunk reuses the first chunk's program."""
    built = _model(exposure=0.0).build()
    attr = built.state.pool.attrs["exposure"]
    assert attr.dtype == jnp.float32 and not attr.weak_type
    counts = _traced_chunks(built, tmp_path)
    assert counts[spans.TRACE_SCHEDULE] == 1
