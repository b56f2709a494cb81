"""CPU rehearsals of whole runs: every cell of BENCHMARK.json at a tiny
size, and a cell on a 2x2 mesh of four virtual devices, which no cell of
BENCHMARK.json uses yet."""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def harness(args, env_extra=None, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, os.path.join(BENCH, "run.py")]
                          + args, env=env, capture_output=True, text=True,
                          timeout=timeout, cwd=ROOT)


@pytest.mark.parametrize("cell", cells())
def test_no_tpu_no_result(cell):
    p = harness(["--workload", cell, "--seed", "1", "--seconds", "1",
                 "--trace", "0"])
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("trace", ["0", "1"])
@pytest.mark.parametrize("cell", cells())
def test_rehearsal_runs_and_prints_no_result(cell, trace):
    p = harness(["--workload", cell, "--seed", str(2**31 + 9), "--seconds",
                 "0.5", "--trace", trace, "--agents", "2048"])
    assert p.returncode == 1, p.stderr[-3000:]
    assert p.stdout.strip() == ""
    assert "rehearsal on cpu passed" in p.stderr, p.stderr[-3000:]


def test_mesh_cell_on_four_virtual_devices():
    """The 2x2 configuration runs end to end through the harness's mesh
    path (set-up, window, trace, check) on four CPU devices."""
    code = f"""
import sys, json
sys.path[:0] = [{os.path.join(ROOT, 'src')!r}, {BENCH!r}]
import run as h
with open({os.path.join(ROOT, 'BENCHMARK.json')!r}) as f:
    bench = json.load(f)
cell = h.Cell.from_entry({{"name": "soma_clustering_2x2.uniform",
    "config": "soma_clustering_2x2", "traffic": "uniform", "chips": 4}}, bench)
r = h.run_cell(cell, seed=7, seconds=0.0, trace=True, agents=4 * 1024)
print(json.dumps({{"checks": r["checks"], "health": r["health"],
                  "steps": r["steps"]}}))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["steps"] == 1
    assert out["health"]["migrate_overflow"] == 0
    assert out["health"]["halo_overflow"] == 0
    assert {"position_gap", "substance_gap", "exposure_gap",
            "halo_overflow", "migrate_overflow"} <= set(out["checks"])
    assert "layout_mismatch" not in out["checks"]
    print(out)
