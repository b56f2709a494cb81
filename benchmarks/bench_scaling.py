"""Fig 6.8/6.9 analog: weak scaling of the distributed engine.

Paper: TeraAgent weak-scales to 84'096 cores — runtime per iteration stays
~flat as servers and agents grow together.  Without real hardware, the
scalable/non-scalable distinction lives in the *per-device communication
volume*: if halo bytes per device are constant in mesh size, the engine
weak-scales (each device exchanges with a bounded neighborhood regardless
of total devices).  We lower the distributed step at several mesh sizes in
subprocesses (fake devices) and extract per-device collective bytes."""

import json
import os
import subprocess
import sys
import tempfile

from .common import print_table, save_result, smoke

_PROBE = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%(ndev)d"
sys.path.insert(0, %(src)r)
import jax, jax.numpy as jnp
import numpy as np
from repro.core import EngineConfig, ForceParams, brownian_motion
from repro.core.distributed import DomainConfig, init_dist_state, make_distributed_step
from repro.launch.dryrun import collective_bytes_from_hlo, _strip_done_ops

mx, my = %(mx)d, %(my)d
from repro.launch.mesh import make_mesh
mesh = make_mesh((mx, my), ("data", "model"))
dcfg = DomainConfig(mesh_axes=("data", "model"), axis_sizes=(mx, my),
                    extent=16.0, halo_width=2.0, halo_capacity=128,
                    migrate_capacity=64, depth=16.0, halo_codec="int16")
spec = dcfg.grid_spec(box_size=2.0, max_per_cell=32)
ecfg = EngineConfig(spec=spec, behaviors=(brownian_motion(0.05),),
                    force_params=ForceParams(), dt=0.05,
                    min_bound=0.0, max_bound=16.0, sort_frequency=8)
rng = np.random.default_rng(0)
n_per_dev = 400
n = n_per_dev * mx * my
pos = rng.uniform(0.5, [mx*16.0-0.5, my*16.0-0.5, 15.5], (n, 3)).astype(np.float32)
state = init_dist_state(dcfg, capacity=1024, positions=pos, diameter=1.2)
step = make_distributed_step(mesh, dcfg, ecfg)
lowered = step.lower(state)
compiled = lowered.compile()
coll = collective_bytes_from_hlo(_strip_done_ops(compiled.as_text()))
ca = compiled.cost_analysis()
print(json.dumps({"ndev": mx*my, "coll": coll, "flops": ca.get("flops", 0.0)}))
"""


def run(fast: bool = True):
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    meshes = [(2, 2), (4, 2), (4, 4)] if fast else [(2, 2), (4, 2), (4, 4), (8, 4)]
    if smoke():
        meshes = [(2, 2), (4, 2)]
    rows, out = [], {}
    for mx, my in meshes:
        code = _PROBE % {"ndev": mx * my, "mx": mx, "my": my, "src": os.path.abspath(src)}
        proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                              text=True, timeout=900)
        if proc.returncode != 0:
            print(proc.stderr[-2000:])
            raise RuntimeError(f"scaling probe {mx}x{my} failed")
        rec = json.loads(proc.stdout.strip().splitlines()[-1])
        per_dev = rec["coll"]["total"]
        rows.append([f"{mx}×{my}", mx * my, f"{per_dev/1e6:.2f} MB",
                     f"{rec['coll']['collective-permute']/1e6:.2f} MB"])
        out[f"{mx}x{my}"] = per_dev
    print_table("Fig 6.9: weak scaling — per-device collective bytes "
                "(constant agents/device)", rows,
                ["mesh", "devices", "total coll bytes/dev", "ppermute bytes/dev"])
    vals = list(out.values())
    growth = vals[-1] / vals[0]
    print(f"per-device communication growth {len(vals[0:])} meshes: {growth:.2f}× "
          f"(flat ≈ 1.0 ⇒ weak-scalable)")
    save_result("scaling", out)
    return growth
