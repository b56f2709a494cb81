"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME]

| module                | paper artifact                                  |
|-----------------------|--------------------------------------------------|
| bench_complexity      | Fig 5.7  runtime/space vs #agents               |
| bench_ablation        | Fig 5.9/5.10 optimization ablation              |
| bench_neighbor_search | Fig 5.13 neighbor-search comparison             |
| bench_use_cases       | Table 4.5 use-case performance                  |
| bench_halo_packing    | Fig 6.10 serialization (tailored packing)       |
| bench_delta_encoding  | Fig 6.11 delta-encoding transfer reduction      |
| bench_scaling         | Fig 6.8/6.9 weak scaling (collective bytes)     |
| bench_sort_frequency  | Fig 5.14 sorting frequency sweep                |
| bench_moe_token_sort  | beyond-paper: §5.4.2 sorting → MoE dispatch     |
| bench_fused_force     | DESIGN.md §4 fused cell-list force HBM bytes    |
| bench_dist_fused      | §6.2 distributed fused force + sort-free packing|
| bench_morton_layout   | §5.4.2 sort-free Z-order layout × morton tiles  |
| bench_many_sim        | DESIGN.md §8 many-sim serving vs sequential     |

Smoke tier: `scripts/bench.sh` (BENCH_SMOKE=1) shrinks problem sizes so every
target executes end-to-end in minutes — benchmark bit-rot fails fast in CI.

Roofline numbers come from `python -m repro.launch.dryrun --all` (separate
entry point: it needs 512 fake devices).
"""

import argparse
import sys
import time
import traceback

from repro.launch.compile_cache import enable_compile_cache

from . import (
    bench_ablation,
    bench_complexity,
    bench_delta_encoding,
    bench_dist_fused,
    bench_fused_force,
    bench_halo_packing,
    bench_many_sim,
    bench_moe_token_sort,
    bench_morton_layout,
    bench_neighbor_search,
    bench_scaling,
    bench_sort_frequency,
    bench_use_cases,
)

ALL = {
    "complexity": bench_complexity,
    "ablation": bench_ablation,
    "neighbor_search": bench_neighbor_search,
    "use_cases": bench_use_cases,
    "sort_frequency": bench_sort_frequency,
    "halo_packing": bench_halo_packing,
    "delta_encoding": bench_delta_encoding,
    "scaling": bench_scaling,
    "moe_token_sort": bench_moe_token_sort,
    "fused_force": bench_fused_force,
    "dist_fused": bench_dist_fused,
    "morton_layout": bench_morton_layout,
    "many_sim": bench_many_sim,
}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true", help="larger problem sizes")
    ap.add_argument("--only", default=None, help="run a single benchmark")
    args = ap.parse_args(argv)

    names = [args.only] if args.only else list(ALL)
    failures = []
    for name in names:
        mod = ALL[name]
        print(f"\n##### {name} " + "#" * (60 - len(name)))
        t0 = time.time()
        try:
            mod.run(fast=not args.full)
            print(f"[{name}] done in {time.time()-t0:.1f}s")
        except Exception:
            traceback.print_exc()
            failures.append(name)
    if failures:
        print(f"\nFAILED benchmarks: {failures}")
        sys.exit(1)
    print("\nAll benchmarks completed.")


if __name__ == "__main__":
    enable_compile_cache()
    main()
