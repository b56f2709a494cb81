"""Readings that the check's limits are set from, on the chip at a cell's
own size: for each seed, one run of the cell with a one-chunk window, its
compared numbers (the program against the float32 reference), and the
control's (the reference computed in bfloat16, put in the program's place,
against the float32 reference on the same input state).  Where the
configuration's check has a ``witness`` (the reference with the program's
own semantics where they depart from the model), the row also compares the
program with it.

    python bench/calibrate.py --workload <cell> --seeds 101 102 ... [--control 3]
        [--out readings.jsonl]

A cell that ``BENCHMARK.json`` does not list is read from its files as
``<config>.<traffic>``.  Prints one JSON line per seed (and appends them to
``--out``).  The benchmark's runs do not run this; PERF.md gives the
readings and the limits set from them.
"""

from __future__ import annotations

import argparse
import json
import sys

import run as harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3,
                    help="seeds (the first ones) that also read the control")
    ap.add_argument("--agents", type=int, default=None)
    ap.add_argument("--out", default=None, help="append the rows here")
    args = ap.parse_args(argv)

    cell = harness.Cell.load(args.workload, held=True)
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu" and args.agents is None:
        print("no TPU: pass --agents to rehearse", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    rows = []
    for i, seed in enumerate(args.seeds):
        r = harness.run_cell(cell, seed, 0.0, False, args.agents,
                             keep_states=True)
        row = {"workload": cell.name, "seed": seed,
               "program": {k: c["value"] for k, c in r["checks"].items()},
               "health": r["health"], "setup_s": r["setup_s"]}
        before, got, want = r["states"][0]
        chunk = cell.params["chunk_steps"]
        if i < args.control:
            low = cell.check.reference(r["cfg"], before, chunk, jnp.bfloat16)
            row["control"] = cell.check.compare(r["cfg"], low, want)
        if hasattr(cell.check, "witness"):
            own = cell.check.witness(r["cfg"], before, chunk)
            row["program_vs_witness"] = cell.check.compare(r["cfg"], got, own)
        print(json.dumps(row), flush=True)
        rows.append(row)
        del r
    if args.out:
        with open(args.out, "a") as f:
            f.writelines(json.dumps(row) + "\n" for row in rows)
    return 0


if __name__ == "__main__":
    sys.exit(main())
