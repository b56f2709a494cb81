"""A run whose timed path is broken underneath has to come out not correct.

Each test drives the rest of a run (set-up, window, the check against the
plain reference) on the CPU at a tiny size, past the harness's look for a
chip, with one fault planted in every window chunk's output.
"""

import dataclasses
import json
import os

import jax.numpy as jnp
import pytest

import run as harness

with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]
SUBSTANCE_CELLS = [c for c in CELLS
                   if harness.Cell.load(c).cfg.get("substances")]
AGENTS = 2048


def by_tag_from(before, after, take):
    """Per slot of ``after``: the same agent's row of ``before``."""
    tag_b = before.pool.attrs["tag"]
    slot = jnp.zeros_like(tag_b).at[tag_b].set(jnp.arange(tag_b.shape[0]))
    return take(before.pool)[slot[after.pool.attrs["tag"]]]


def unchanged(before, after):
    """The step returns its state unchanged."""
    return before


def half_left_out(before, after):
    """Half of the agents (tags of the upper half) are not stepped."""
    tag = after.pool.attrs["tag"]
    stale = tag >= tag.shape[0] // 2
    pos = by_tag_from(before, after, lambda p: p.position)
    pool = after.pool.replace(
        position=jnp.where(stale[:, None], pos, after.pool.position))
    return dataclasses.replace(after, pool=pool)


def one_altered(before, after):
    """One agent's answer is altered where it is produced: its position
    moves by half a unit and, for SIR, its state flips."""
    pool = after.pool
    pool = pool.replace(position=pool.position.at[7, 0].add(0.5),
                        kind=pool.kind.at[7].set(1 - jnp.minimum(pool.kind[7], 1)))
    return dataclasses.replace(after, pool=pool)


def overflowed(before, after):
    """The step reports an over-full cell in its health counters."""
    health = dataclasses.replace(
        after.health, cell_overflow_steps=after.health.cell_overflow_steps + 1)
    return dataclasses.replace(after, health=health)


def dose_at_chunk_start(cfg):
    """The step's exposure dose is sampled at the chunk-start positions,
    not at the end-of-step ones (the same grids, the same agents)."""
    from repro.core import concentration_at

    def planted(before, after):
        pool = after.pool
        start = by_tag_from(before, after, lambda p: p.position)

        def dose(pos):
            own = jnp.zeros(pool.kind.shape, jnp.float32)
            for sub in cfg["substances"]:
                c = concentration_at(after.grids[sub["name"]], pos)
                own = jnp.where(pool.kind == sub["kind"], c, own)
            return jnp.where(pool.alive, own * cfg["dt"], 0.0)

        exposure = pool.get("exposure") - dose(pool.position) + dose(start)
        return dataclasses.replace(after,
                                   pool=pool.set_attr("exposure", exposure))

    return planted


def only_last(fault):
    """The fault in the window's last chunk alone: the first compared chunk
    stays sound."""
    seen = []

    def planted(before, after):
        seen.append(None)
        return fault(before, after) if len(seen) == 3 else after

    return planted


def run(cell, fault=None, chunks=1):
    r = harness.run_cell(harness.Cell.load(cell), seed=2**31 + 17,
                         seconds=0.0, trace=False, agents=AGENTS,
                         fault=fault, chunks=chunks)
    return {k: c["value"] <= c["limit"] for k, c in r["checks"].items()}


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    assert all(run(cell).values())


@pytest.mark.parametrize("fault", [unchanged, half_left_out, one_altered,
                                   overflowed])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(cell, fault):
    assert not all(run(cell, fault).values())


@pytest.mark.parametrize("cell", SUBSTANCE_CELLS)
def test_dose_at_chunk_start_positions_is_not_correct(cell):
    """The check doses the reference at the program's end-of-step
    positions; a program that doses where its agents started still fails
    (chemotaxis alone moves an agent 0.75 a step against 5-unit voxels)."""
    checks = run(cell, dose_at_chunk_start(harness.Cell.load(cell).cfg))
    assert not checks["exposure_gap"], checks


@pytest.mark.parametrize("cell", CELLS)
def test_fault_in_the_last_chunk_is_not_correct(cell):
    """A window of several chunks compares its last chunk too, not only the
    first (which holds the layout sort)."""
    checks = run(cell, only_last(one_altered), chunks=3)
    assert not all(checks.values())
