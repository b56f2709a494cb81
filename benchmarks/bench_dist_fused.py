"""Distributed fused force pass vs the dense candidate path (DESIGN.md §4).

Companion to ``bench_fused_force.py`` for the *distributed* engine (§6.2):
the per-device ``distributed_step`` is lowered at a fixed mesh for each force
impl and accounted with ``cost_analysis()`` "bytes accessed" — the HBM-traffic
proxy that is the tracked metric in this container (interpret-mode wall time
is not representative, see bench_fused_force).  Variants:

  dense:          force_impl="reference" — builds the (C, 27M) candidate
                  tensor over the ghost-extended arrays and gathers (C, K, 3)
                  candidate positions (the pre-adoption dataflow)
  fused:          force_impl="fused", overflow fallback disabled — the
                  Pallas cell-list kernel walks the halo-extended grid
                  directly; the lazy NeighborContext means the candidate
                  tensor is never materialized (cost_analysis bills both
                  lax.cond branches, so the fallback variant is reported
                  separately)
  fused_fallback: force_impl="fused" with the lax.cond dense fallback kept
                  (the production-default safety net)

Also reported: sort-op counts.  The migrate/halo packing subgraph must be
ZERO-sort (channel selection and free-slot insertion are cumsum-rank
compaction scatters — ISSUE 2); since ISSUE 5 the ghost-extended grid build
ranks via the sort-free tiled-histogram pass (`repro.kernels.cell_rank`);
and since ISSUE 8 the §5.4.2 layout sort is itself a sort-free counting-sort
permutation — so EVERY variant, sort op gated (sf=8), off (sf=0,
``fused_sort_off``) or firing every step (sf=1, ``sorted_layout_on``), must
lower the whole per-device step with ZERO HLO sorts.  A standalone argsort
lowering inside each probe is the positive detector control.

The fused variant is probed under both halo delta-codecs (int16 and int8 —
`repro.core.delta` error-feedback quantization; ROADMAP item) so the wire
format's cost shows up in the tracked json next to the baseline.

Acceptance (ISSUE 2): step bytes dense/fused ≥ 3 at N=8192/device, M=16,
and packing_sorts == 0.  Acceptance (ISSUE 5 + 8): step_sorts == 0 on every
variant, including sorted_layout_on.

Each probe runs in a subprocess with 4 fake host devices (the main process
must keep the real single-device view, like tests/test_distributed.py).
"""

import json
import os
import subprocess
import sys

from .common import print_table, save_result

# Smoke sizing comes from scripts/bench.sh's BENCH_N export (single source
# of truth); BENCH_SMOKE itself only reroutes save_result (common.smoke).
N_PER_DEV = int(os.environ.get("BENCH_N", 8192))
MAX_PER_CELL = int(os.environ.get("BENCH_M", 16))

_PROBE = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, %(src)r)
import numpy as np
from repro.core import EngineConfig, ForceParams
from repro.core.distributed import (
    DomainConfig, hlo_sort_count, init_dist_state, make_distributed_step,
    make_packing_program,
)
from repro.launch.mesh import make_mesh

n_per_dev = %(n)d
m = %(m)d
space = 100.0
radius = 6.25  # -> 16 local cells/dim: ~2 agents/cell mean at N=8192/device
mesh = make_mesh((2, 2), ("data", "model"))
dcfg = DomainConfig(
    mesh_axes=("data", "model"), axis_sizes=(2, 2), extent=space,
    halo_width=radius, halo_capacity=max(n_per_dev // 4, 64),
    migrate_capacity=max(n_per_dev // 8, 64), depth=space,
    halo_codec=%(halo_codec)r, overlap_halo=%(overlap)s,
)
spec = dcfg.grid_spec(box_size=radius, max_per_cell=m)
ecfg = EngineConfig(
    spec=spec, behaviors=(), force_params=ForceParams(), dt=0.05,
    min_bound=0.0, max_bound=space, boundary="open",
    sort_frequency=%(sort_frequency)d,
    force_impl=%(impl)r, fused_overflow_fallback=%(fallback)s,
)
rng = np.random.default_rng(0)
n = n_per_dev * 4
pos = rng.uniform(0.0, [2 * space, 2 * space, space], (n, 3)).astype(np.float32)
state = init_dist_state(
    dcfg, capacity=int(n_per_dev * 3 // 2), positions=pos, diameter=4.0
)
step = make_distributed_step(mesh, dcfg, ecfg)
lowered = step.lower(state)   # lowered once: compiled for costs, text for sorts
compiled = lowered.compile()
ca = compiled.cost_analysis()
out = {
    "bytes_accessed": float(ca["bytes accessed"]),
    "flops": float(ca.get("flops", 0.0)),
}


packing_hlo = make_packing_program(mesh, dcfg).lower(state).as_text()
out["packing_sorts"] = hlo_sort_count(packing_hlo)
out["step_sorts"] = hlo_sort_count(lowered.as_text())
# ISSUE 10: def-use reachability over the compiled (scheduled) module —
# which force-pass conditionals have the halo collective as an ancestor.
from repro.core.distributed import hlo_overlap_report
out["overlap"] = hlo_overlap_report(compiled.as_text())
# Positive control: the sort detector must still see a real argsort.
import jax, jax.numpy as jnp
det = jax.jit(jnp.argsort).lower(jnp.zeros((64,), jnp.float32)).as_text()
out["detector_sorts"] = hlo_sort_count(det)
print(json.dumps(out))
"""


def _probe(
    src: str, n: int, m: int, impl: str, fallback: bool,
    sort_frequency: int = 8, halo_codec: str = "int16",
    overlap: bool = False,
) -> dict:
    code = _PROBE % {
        "src": os.path.abspath(src), "n": n, "m": m,
        "impl": impl, "fallback": fallback, "sort_frequency": sort_frequency,
        "halo_codec": halo_codec, "overlap": overlap,
    }
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=900
    )
    if proc.returncode != 0:
        print(proc.stderr[-2000:])
        raise RuntimeError(f"dist_fused probe impl={impl} failed")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def run(fast: bool = True):
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    n = N_PER_DEV
    m = MAX_PER_CELL
    variants = {
        "dense": ("reference", True),
        "fused": ("fused", False),
        "fused_fallback": ("fused", True),
    }
    out = {
        "config": {
            "n_per_device": n, "devices": 4, "max_per_cell": m,
            "candidates_k": 27 * m, "mesh": "2x2", "halo_codec": "int16",
        },
        "step": {},
        "note": (
            "bytes_accessed of the lowered per-device SPMD step "
            "(cost_analysis); interpret-mode wall time is not representative "
            "on this CPU container, bytes is the tracked metric.  "
            "fused_fallback bills BOTH lax.cond branches, so 'fused' (bound "
            "guaranteed by construction) is the acceptance variant."
        ),
    }
    rows = []
    for name, (impl, fb) in variants.items():
        rec = _probe(src, n, m, impl, fb)
        out["step"][name] = rec
        rows.append(
            (f"step/{name}", f"{rec['bytes_accessed']/1e6:.1f}",
             rec["packing_sorts"], rec["step_sorts"])
        )

    # ISSUE 5: the ghost-extended grid build is sort-free, so the step is
    # sort-free with the layout sort gated off (fused_sort_off) ...
    nosort = _probe(src, n, m, "fused", False, sort_frequency=0)
    out["step"]["fused_sort_off"] = nosort
    rows.append(
        ("step/fused_sort_off", f"{nosort['bytes_accessed']/1e6:.1f}",
         nosort["packing_sorts"], nosort["step_sorts"])
    )

    # ... and ISSUE 8: the layout sort itself is sort-free, so the step
    # stays sort-free even firing it EVERY iteration.
    sorted_on = _probe(src, n, m, "fused", False, sort_frequency=1)
    out["step"]["sorted_layout_on"] = sorted_on
    rows.append(
        ("step/sorted_layout_on", f"{sorted_on['bytes_accessed']/1e6:.1f}",
         sorted_on["packing_sorts"], sorted_on["step_sorts"])
    )

    # ROADMAP: the int8 error-feedback halo codec, accounted next to int16.
    int8 = _probe(src, n, m, "fused", False, halo_codec="int8")
    out["step"]["fused_int8_halo"] = int8
    rows.append(
        ("step/fused_int8_halo", f"{int8['bytes_accessed']/1e6:.1f}",
         int8["packing_sorts"], int8["step_sorts"])
    )

    # ISSUE 10: the overlapped halo schedule, compile-only.  The interior
    # force conditional must have ZERO halo-scoped collective-permute
    # ancestors in the scheduled module (XLA may run the exchange
    # concurrently with it); the shell pass is the positive control.
    overlap_on = _probe(src, n, m, "fused", False, overlap=True)
    out["step"]["overlap_on"] = overlap_on
    rows.append(
        ("step/overlap_on", f"{overlap_on['bytes_accessed']/1e6:.1f}",
         overlap_on["packing_sorts"], overlap_on["step_sorts"])
    )

    ratio = (
        out["step"]["dense"]["bytes_accessed"]
        / out["step"]["fused"]["bytes_accessed"]
    )
    out["ratios"] = {"step_bytes_dense_over_fused": ratio}
    out["packing_sorts"] = out["step"]["dense"]["packing_sorts"]

    print_table(
        f"distributed fused force (N={n}/device, M={m}, mesh 2x2)",
        rows, ["variant", "MB accessed/step", "packing sorts", "step sorts"],
    )
    print(f"step_bytes_dense_over_fused: {ratio:.2f}x")
    # Lowering gates (ISSUE 3 + 5 + 8 / scripts/ci.sh smoke tier):
    #   * the migrate/halo packing subgraph stays sort-free under EVERY
    #     variant of the scheduler-built step;
    #   * the whole per-device SPMD program is sort-free in every variant —
    #     layout sort gated (sf=8), off (sf=0), or every-step (sf=1) — now
    #     that §5.4.2 sorting is a counting-sort permutation;
    #   * each probe's standalone argsort control must still register, or
    #     the detector is broken.
    for name, rec in out["step"].items():
        assert rec["detector_sorts"] > 0, f"{name}: sort detector is blind"
        assert rec["packing_sorts"] == 0, f"{name}: packing must be sort-free"
        assert rec["step_sorts"] == 0, (
            f"{name}: whole step must be sort-free, got {rec['step_sorts']}"
        )
    # ISSUE 10 overlap gates (compile-only, def-use reachability on the
    # scheduled HLO): the interior pass never reads the halo collective,
    # the shell pass does (positive control), and the serial schedule's
    # single force pass depends on it (negative control).
    ov = out["step"]["overlap_on"]["overlap"]
    assert ov["halo_collectives"] > 0, "overlap_on: no halo collectives seen"
    assert ov["interior_forces"]["conditionals"] >= 1, (
        "overlap_on: interior force conditional not found"
    )
    assert ov["interior_forces"]["halo_collective_ancestors"] == 0, (
        "overlap_on: halo collective is an ancestor of the interior pass"
    )
    assert ov["shell_forces"]["halo_collective_ancestors"] > 0, (
        "overlap_on: shell pass must depend on the halo collective"
    )
    sv = out["step"]["fused"]["overlap"]
    assert sv["forces"]["conditionals"] >= 1, (
        "serial: force conditional not found"
    )
    assert sv["forces"]["halo_collective_ancestors"] > 0, (
        "serial: force pass must depend on the halo collective"
    )
    print(
        "overlap probe: interior halo-ancestors="
        f"{ov['interior_forces']['halo_collective_ancestors']} "
        f"shell={ov['shell_forces']['halo_collective_ancestors']} "
        f"serial forces={sv['forces']['halo_collective_ancestors']}"
    )
    path = save_result("dist_fused_force", out)
    print("saved:", path)
    return out


if __name__ == "__main__":
    run(fast="--full" not in sys.argv)
