"""Shared benchmark utilities.

``repro`` resolves via the installed package (``pip install -e .``) or the
PYTHONPATH=src the scripts/ entry points export — no sys.path mutation here.
"""

import json
import os
import time

import jax
import numpy as np

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "results", "bench")


def smoke() -> bool:
    """Smoke tier (scripts/bench.sh): shrink problem sizes / iteration counts
    so every benchmark target executes end-to-end in minutes.  Results are
    NOT representative — the tier exists so benchmark bit-rot fails fast."""
    return os.environ.get("BENCH_SMOKE") == "1"


def save_result(name: str, payload: dict):
    """Persist a benchmark payload.  Smoke runs are tagged and diverted to
    results/bench/smoke/ so they can never clobber a tracked result."""
    out_dir = RESULTS_DIR
    if smoke():
        out_dir = os.path.join(RESULTS_DIR, "smoke")
        payload = dict(payload)
        payload["smoke"] = True
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    return path


def timeit(fn, *args, warmup=1, iters=3):
    """Median wall time of fn(*args) with block_until_ready."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def print_table(title: str, rows, headers):
    print(f"\n== {title} ==")
    widths = [max(len(str(h)), max((len(str(r[i])) for r in rows), default=0))
              for i, h in enumerate(headers)]
    print("  ".join(str(h).ljust(w) for h, w in zip(headers, widths)))
    for r in rows:
        print("  ".join(str(c).ljust(w) for c, w in zip(r, widths)))


def bytes_and_sorts(jitted, *args):
    """(bytes accessed, HLO sort-op count) from ONE lowering of a jitted
    callable — the shared compile-only probe behind the smoke tier's
    lowering guards (no execution)."""
    from repro.core.distributed import hlo_sort_count

    lowered = jitted.lower(*args)
    ca = lowered.compile().cost_analysis()
    return float(ca["bytes accessed"]), hlo_sort_count(lowered.as_text())


def argsort_build_index(spec, position, alive):
    """Seed-era argsort grid build, kept as the benchmarks' bytes/sort
    BASELINE (what ISSUE 5 removed from the hot path): bench_neighbor_search
    accounts it against the sort-free build, bench_fused_force's seed-step
    emulation builds through it so the tracked seed baseline keeps the seed
    engine's dataflow.  The bit-exactness oracle copy used by the parity
    suite lives in tests/grid_oracle.py — never import either from src."""
    import jax.numpy as jnp

    from repro.core.grid import GridIndex, cell_coords, linear_cell_id

    c = position.shape[0]
    n_cells = spec.n_cells
    cid = jnp.where(
        alive, linear_cell_id(spec, cell_coords(spec, position)), n_cells
    )
    order = jnp.argsort(cid, stable=True)
    sorted_cid = cid[order]
    pos = jnp.arange(c, dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_cid[1:] != sorted_cid[:-1]]
    )
    run_start = jax.lax.cummax(jnp.where(is_start, pos, -1))
    rank = jnp.zeros((c,), jnp.int32).at[order].set(pos - run_start)

    counts = jnp.zeros((n_cells + 1,), jnp.int32).at[cid].add(1)
    cell_count = counts[:n_cells]
    m = spec.max_per_cell
    valid = alive & (rank < m)
    flat_idx = jnp.where(valid, cid * m + rank, n_cells * m)
    cell_list = jnp.full((n_cells * m + 1,), c, jnp.int32)
    cell_list = cell_list.at[flat_idx].set(
        jnp.arange(c, dtype=jnp.int32), mode="drop"
    )[: n_cells * m].reshape(n_cells, m)
    return GridIndex(
        cell_of_agent=cid.astype(jnp.int32),
        cell_list=cell_list,
        cell_count=cell_count,
        overflowed=jnp.any(cell_count > m),
        slot_of_agent=flat_idx,
    )
