"""The control — the plain reference computed in bfloat16 and put in the
program's place — has to fail the check, at a size a test run can hold."""

import json
import os

import jax.numpy as jnp
import pytest

import run as harness


def cells():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("cell", cells())
def test_control_fails_the_limits(cell):
    c = harness.Cell.load(cell)
    for seed in (5, 6, 2**31 + 3):
        r = harness.run_cell(c, seed, 0.0, False, agents=2048,
                             keep_states=True)
        assert all(v["value"] <= v["limit"] for v in r["checks"].values())
        (before, _, want), = r["states"]
        low = c.check.reference(r["cfg"], before, c.params["chunk_steps"],
                                jnp.bfloat16)
        gaps = c.check.compare(r["cfg"], low, want)
        assert any(gaps[k] > c.check.LIMITS[k] for k in gaps), gaps
