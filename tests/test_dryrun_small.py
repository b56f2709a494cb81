"""Dry-run machinery test on a small fake-device mesh (subprocess).

Covers the lower→compile→cost/collective-extraction path end to end for one
cell of each step kind, at 16 fake devices so it runs in seconds."""

import os
import subprocess
import sys

import pytest

_SCEN = r"""
import os, sys, json
os.environ["DRYRUN_XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
sys.path.insert(0, %(src)r)
import repro.launch.dryrun as dr
import dataclasses
import jax
from repro.configs import get_config, reduced_config, SHAPES
from repro.launch.mesh import make_mesh

mesh = make_mesh((4, 4), ("data", "model"))

# tiny-but-structured config; shapes stay the assigned ones so the sharding
# divisibility logic is exercised
cfg = dataclasses.replace(
    reduced_config("gemma-7b"),
    d_model=128, n_heads=8, n_kv_heads=8, head_dim=16, d_ff=256,
    vocab_size=2048, n_layers=2, dtype="bfloat16", remat=True,
    attention_block_q=512, attention_block_k=1024,
)

for shape_name in ("train_4k", "decode_32k"):
    shape = SHAPES[shape_name]
    lowered = dr.lower_cell("gemma-7b", shape_name, mesh, cfg=cfg)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    assert cost.get("flops", 0) > 0
    hlo = dr._strip_done_ops(compiled.as_text())
    coll = dr.collective_bytes_from_hlo(hlo)
    fused = dr.fused_bytes_from_hlo(hlo)
    assert fused > 0
    mem = compiled.memory_analysis()
    print(shape_name, "ok", int(cost["flops"]), int(coll["total"]))
print("SMALL DRYRUN OK")
"""


@pytest.mark.subprocess
@pytest.mark.slow
def test_small_mesh_dryrun():
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    code = _SCEN % {"src": src}
    env = dict(os.environ)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=560, env=env)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "SMALL DRYRUN OK" in proc.stdout
