"""Fused cell-list force pass vs the dense candidate paths (DESIGN.md §4).

Two levels, both accounted with ``jax.jit(...).lower().compile().
cost_analysis()`` ("bytes accessed" — the HBM-traffic proxy the BioDynaMo /
PhysiCell analyses say actually limits the force pass) plus median wall time:

  * stage level — just the force evaluation from a built index:
      dense:  (N, 27M) candidate build + (N, K, 3) gather + jnp force chain
      tiled:  same candidates, lax.map over agent tiles (bounded working set)
      fused:  repro.kernels.cell_force straight from the cell list
  * step level — one full ``simulation_step``:
      seed:   emulation of the seed dataflow (candidates built TWICE — once
              in the step, once in mechanical_forces — plus the (N, 27M)
              static-flag gather), the baseline the acceptance ratio is
              against
      dense:  today's reference path (duplicate-candidate fix included)
      fused:  force_impl="fused" with the overflow fallback disabled (the
              max_per_cell bound is guaranteed by construction here;
              cost_analysis counts both lax.cond branches, so leaving the
              fallback in would bill the dense path it exists to avoid —
              the `step_fused_fallback` variant keeps it for reference)

Acceptance (ISSUE 1): step-level bytes ratio seed/fused ≥ 3 at N=8192,
max_per_cell=16.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from benchmarks.common import (
    RESULTS_DIR,
    argsort_build_index,
    bytes_and_sorts,
    print_table,
    save_result,
    timeit,
)

from repro.core import EngineConfig, ForceParams, init_state, make_pool, simulation_step
from repro.core.forces import (
    forces_from_candidates,
    forces_from_candidates_tiled,
    update_static_flags,
)
from repro.core.grid import build_index, candidate_neighbors, spec_for_space
from repro.kernels.cell_force import ops as cf_ops

N = int(os.environ.get("BENCH_N", 8192))
MAX_PER_CELL = int(os.environ.get("BENCH_M", 16))
SPACE = 100.0
RADIUS = 6.25  # -> 16^3 cells at SPACE=100: ~2 agents/cell mean at N=8192


def _bytes_accessed(jitted, *args):
    ca = jitted.lower(*args).compile().cost_analysis()
    return float(ca["bytes accessed"])


def _setup():
    rng = np.random.default_rng(0)
    pos = rng.uniform(0, SPACE, (N, 3)).astype(np.float32)
    diam = rng.uniform(2.0, 6.0, (N,)).astype(np.float32)
    pool = make_pool(N, jnp.asarray(pos), diameter=jnp.asarray(diam))
    spec = spec_for_space(0.0, SPACE, RADIUS, max_per_cell=MAX_PER_CELL)
    return pool, spec


# ------------------------------------------------------------- stage level

def _stage_fns(spec, params):
    def dense(pool, index):
        cand, mask = candidate_neighbors(spec, index, pool)
        return forces_from_candidates(pool.position, pool.radius(), cand, mask, params)

    def tiled(pool, index):
        cand, mask = candidate_neighbors(spec, index, pool)
        return forces_from_candidates_tiled(
            pool.position, pool.radius(), cand, mask, params,
            pool.position, pool.radius(), tile=512, unroll=False,
        )

    def fused(pool, index):
        return cf_ops.cell_list_force(
            pool.position, pool.radius(), index.cell_list,
            index.slot_of_agent, spec.dims,
            k=params.repulsion_k, gamma=params.attraction_gamma,
        )

    return {"dense": dense, "tiled": tiled, "fused": fused}


# -------------------------------------------------------------- step level

def _seed_step(spec, params, pool_state):
    """The seed engine's force-step dataflow: candidates materialized twice
    (simulation_step + mechanical_forces), (N, 27M) static detection, and
    the argsort grid build (`common.argsort_build_index`) — the baseline
    must keep the seed's build, not inherit the ISSUE-5 sort-free one, or
    the tracked seed/fused ratio stops measuring the seed engine."""
    pool = pool_state
    index = argsort_build_index(spec, pool.position, pool.alive)
    cand, cand_mask = candidate_neighbors(spec, index, pool)       # step copy
    cand2, mask2 = candidate_neighbors(spec, index, pool)          # forces copy
    force = forces_from_candidates(pool.position, pool.radius(), cand2, mask2, params)
    force = jnp.where(pool.alive[:, None], force, 0.0)
    new_pos = jnp.clip(pool.position + force * 0.1, 0.0, SPACE)
    disp = new_pos - pool.position
    pool = pool.replace(position=new_pos)
    pool = update_static_flags(pool, disp, cand, cand_mask, params)
    return pool.replace(age=pool.age + jnp.where(pool.alive, 0.1, 0.0))


def _engine_step(spec, impl, fallback, sort_frequency=0, **kw):
    config = EngineConfig(
        spec=spec,
        force_params=ForceParams(),
        dt=0.1,
        min_bound=0.0,
        max_bound=SPACE,
        boundary="closed",
        sort_frequency=sort_frequency,
        force_impl=impl,
        fused_overflow_fallback=fallback,
        **kw,
    )
    return functools.partial(simulation_step, config)


def guard(tol: float = 0.05):
    """Scheduler-path regression guard (ISSUE 3): re-probe the fused engine
    step at the TRACKED problem size (compile-only — cost_analysis needs no
    execution, so this is cheap even under BENCH_SMOKE shrinkage) and assert
    bytes/step within ``tol`` of results/bench/fused_force.json.  A schedule
    refactor that reintroduces candidate materialization or duplicates a
    pipeline stage fails here immediately.

    The baseline is read from the git-COMMITTED copy of the tracked json
    when available (falling back to the working-tree file): ``run()``
    rewrites the tracked file right after this check, so comparing against
    the working tree would let a <5%-per-run regression ratchet the
    baseline along with itself across successive full runs."""
    import json
    import subprocess

    path = os.path.join(RESULTS_DIR, "fused_force.json")
    ref = None
    try:
        committed = subprocess.run(
            ["git", "show", "HEAD:results/bench/fused_force.json"],
            capture_output=True, text=True, timeout=30,
            cwd=os.path.join(os.path.dirname(__file__), ".."),
        )
        if committed.returncode == 0:
            ref = json.loads(committed.stdout)
            print("guard: baseline = committed results/bench/fused_force.json")
    except (OSError, subprocess.SubprocessError, json.JSONDecodeError):
        ref = None
    if ref is None:
        if not os.path.exists(path):
            print("guard: no tracked fused_force.json yet — skipping")
            return None
        with open(path) as f:
            ref = json.load(f)
        print("guard: baseline = working-tree results/bench/fused_force.json")
    n, m = ref["config"]["n"], ref["config"]["max_per_cell"]
    want = ref["step"]["fused"]["bytes_accessed"]

    rng = np.random.default_rng(0)
    pos = rng.uniform(0, SPACE, (n, 3)).astype(np.float32)
    diam = rng.uniform(2.0, 6.0, (n,)).astype(np.float32)
    pool = make_pool(n, jnp.asarray(pos), diameter=jnp.asarray(diam))
    spec = spec_for_space(0.0, SPACE, RADIUS, max_per_cell=m)
    state = init_state(pool, seed=0)
    got, sorts = bytes_and_sorts(jax.jit(_engine_step(spec, "fused", False)), state)

    rel = abs(got - want) / want
    print(f"guard: scheduler-path fused step (N={n}, M={m}) = {got/1e6:.1f} MB "
          f"vs tracked {want/1e6:.1f} MB ({rel*100:.2f}% drift, tol {tol*100:.0f}%), "
          f"sorts={sorts}")
    assert rel <= tol, (
        f"fused step bytes drifted {rel*100:.1f}% from the tracked result — "
        "the scheduler refactor changed the step dataflow"
    )
    # ISSUE 5: with the §5.4.2 sort gated off (sort_frequency=0 here) the
    # whole single-node step must lower WITHOUT any sort op — the grid
    # build's argsort was the last one on the hot path.
    assert sorts == 0, (
        f"fused step lowered with {sorts} sort ops — a sort crept back into "
        "the per-step hot path (grid build / packing / compaction?)"
    )
    return got


def run(fast: bool = True):
    pool, spec = _setup()
    params = ForceParams()
    index = build_index(spec, pool)
    assert not bool(index.overflowed), "benchmark grid overflowed; raise BENCH_M"
    out = {
        "config": {
            "n": N, "max_per_cell": MAX_PER_CELL, "dims": list(spec.dims),
            "candidates_k": 27 * MAX_PER_CELL,
        },
        "stage": {}, "step": {},
        "note": (
            "bytes_accessed is the target metric: the Pallas kernel runs in "
            "interpret mode on this CPU container, so fused wall_s reflects "
            "the interpreter's emulated grid loop, not the Mosaic lowering "
            "the kernel targets; the dense paths are native XLA:CPU."
        ),
    }

    rows = []
    for name, fn in _stage_fns(spec, params).items():
        jitted = jax.jit(fn)
        b = _bytes_accessed(jitted, pool, index)
        t = timeit(jitted, pool, index, warmup=1, iters=3)
        out["stage"][name] = {"bytes_accessed": b, "wall_s": t}
        rows.append((f"stage/{name}", f"{b/1e6:.1f}", f"{t*1e3:.1f}"))

    state = init_state(pool, seed=0)
    steps = {
        "seed": (jax.jit(functools.partial(_seed_step, spec, params)), (pool,)),
        "dense": (jax.jit(_engine_step(spec, "reference", True)), (state,)),
        "fused": (jax.jit(_engine_step(spec, "fused", False)), (state,)),
        "fused_fallback": (jax.jit(_engine_step(spec, "fused", True)), (state,)),
        # ISSUE 8: §5.4.2 layout sort enabled EVERY step — the sort-free
        # counting-sort permutation must keep the whole step sort-free.
        "sorted_layout_on": (
            jax.jit(_engine_step(spec, "fused", False, sort_frequency=1)),
            (state,),
        ),
    }
    for name, (jitted, args) in steps.items():
        b, sorts = bytes_and_sorts(jitted, *args)
        t = timeit(jitted, *args, warmup=1, iters=3)
        out["step"][name] = {"bytes_accessed": b, "wall_s": t, "step_sorts": sorts}
        rows.append((f"step/{name}", f"{b/1e6:.1f}", f"{t*1e3:.1f}"))
        if name == "seed":
            # The seed emulation keeps the argsort build by design — it
            # doubles as the sort-detector sanity check.
            assert sorts > 0, "seed baseline lost its argsort (detector?)"
        else:
            # Engine steps must lower sort-free: the grid build since
            # ISSUE 5, and — for sorted_layout_on, which enables the §5.4.2
            # layout sort every step — the counting-sort permutation of
            # ISSUE 8.
            assert sorts == 0, f"step/{name}: expected sort-free, got {sorts}"

    out["ratios"] = {
        "step_bytes_seed_over_fused":
            out["step"]["seed"]["bytes_accessed"] / out["step"]["fused"]["bytes_accessed"],
        "step_bytes_dense_over_fused":
            out["step"]["dense"]["bytes_accessed"] / out["step"]["fused"]["bytes_accessed"],
        "stage_bytes_dense_over_fused":
            out["stage"]["dense"]["bytes_accessed"] / out["stage"]["fused"]["bytes_accessed"],
    }
    print_table(
        f"fused cell-list force (N={N}, M={MAX_PER_CELL}, dims={spec.dims})",
        rows, ["variant", "MB accessed", "ms"],
    )
    for k, v in out["ratios"].items():
        print(f"{k}: {v:.2f}x")
    guarded = guard()
    if guarded is not None:
        out["guard"] = {"scheduler_path_fused_bytes": guarded, "tol": 0.05}
    path = save_result("fused_force", out)
    print("saved:", path)
    return out


if __name__ == "__main__":
    run(fast="--full" not in sys.argv)
