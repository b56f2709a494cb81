#!/usr/bin/env bash
# Single CI gate (see ROADMAP.md): tier-1 tests, then the benchmark smoke
# tier.
#
#   scripts/ci.sh            # full gate
#   scripts/ci.sh -m "not slow"   # extra args forwarded to tier-1 pytest
#
# Tier 1 (scripts/test.sh) is the correctness bar: the full pytest suite on
# 8 fake host devices.  The smoke tier (scripts/bench.sh) runs every
# benchmarks/run.py target end-to-end at shrunk sizes so benchmark bit-rot
# and API drift fail fast; it now also carries the lowering assertions that
# guard the scheduler refactor surface:
#   * bench_dist_fused asserts the migrate/halo packing subgraph lowers with
#     ZERO sort ops (hlo_sort_count) — a schedule change that reintroduces a
#     sort into packing fails here, not on the next hardware run;
#   * bench_fused_force re-probes the fused step at the tracked size
#     (compile-only cost_analysis) and asserts bytes/step within 5% of
#     results/bench/fused_force.json;
#   * bench_morton_layout.guard() re-probes the morton-window acceptance
#     row the same way (5% drift vs results/bench/morton_layout.json,
#     ≥1.3x bytes win vs linear fused, zero HLO sorts at sort_frequency=1);
#   * bench_sort_frequency asserts the whole step lowers with ZERO sorts at
#     EVERY sort_frequency — the §5.4.2 layout sort must stay a
#     counting-sort permutation (ISSUE 8);
#   * bench_many_sim asserts slot-vs-solo bit-exactness of the batched
#     serving scan and re-probes batched bytes/step/sim at the tracked
#     width (5% drift vs results/bench/many_sim.json, DESIGN.md §8).
# The example smoke tier (scripts/examples.sh) runs each use-case example a
# handful of steps through the `Simulation` model API (DESIGN.md §6).
# The kill-and-resume tier (DESIGN.md §7) SIGKILLs a checkpointed run
# mid-flight, resumes it from disk, and asserts the recovered observable
# series hashes identically to an uninterrupted run.
# The serving tier (DESIGN.md §8) continuous-batches 3 sessions over the
# slot pool, evicts a NaN-bombed one on its per-slot HealthReport, and
# asserts the survivors' series hash identically to solo runs.
# The overlapped-halo tier (ISSUE 10, DESIGN.md §4) runs the serial and
# overlapped distributed schedules on the full 8-device (4×2) mesh and
# asserts their final-state sha256 hashes are identical — the bit-exactness
# contract behind DomainConfig.overlap_halo.
set -euo pipefail
cd "$(dirname "$0")/.."
# Every tier is a CPU tier: on a machine with a TPU, a parent holding the
# chip would make every subprocess that reaches for it fail on libtpu's lock.
export JAX_PLATFORMS=cpu

echo "=== CI tier 0: test deps ==="
# Property tests want the real hypothesis engine (pyproject `[test]` extra).
# Offline/bare containers fall back to the bundled executor in
# tests/conftest.py, which still RUNS every @given test (no stub skips) —
# the install is best-effort, never a gate.
if python -c "import hypothesis" 2>/dev/null; then
    echo "hypothesis: real engine available"
elif python -m pip install --quiet --disable-pip-version-check \
        --retries 0 --timeout 5 hypothesis 2>/dev/null; then
    echo "hypothesis: installed (the [test] extra's missing dep; pins, if" \
         "ever added there, must be mirrored here)"
else
    echo "hypothesis: pip unavailable — property tests run on the bundled" \
         "fallback executor (tests/conftest.py)"
fi

echo
echo "=== CI tier 1: tests ==="
scripts/test.sh "$@"

echo
echo "=== CI tier 2: benchmark smoke ==="
scripts/bench.sh

echo
echo "=== CI tier 3: example smoke (model API) ==="
scripts/examples.sh

echo
echo "=== CI tier 4: kill-and-resume smoke (fault tolerance) ==="
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
CKPT_DIR="$(mktemp -d)"
trap 'rm -rf "$CKPT_DIR"' EXIT
SIR="examples/epidemiology_sir.py"
REF_SHA=$(python "$SIR" --smoke | grep '^counts sha256=')
echo "uninterrupted: $REF_SHA"
# SIGKILL mid-run, right after the checkpoint at step >= 6 lands.
if python "$SIR" --smoke --checkpoint-dir "$CKPT_DIR" --kill-at 6; then
    echo "FAIL: --kill-at 6 run was expected to die mid-run" >&2
    exit 1
fi
# Same command minus --kill-at resumes from the surviving checkpoint.
RES_SHA=$(python "$SIR" --smoke --checkpoint-dir "$CKPT_DIR" \
    | grep '^counts sha256=')
echo "resumed:       $RES_SHA"
if [ "$REF_SHA" != "$RES_SHA" ]; then
    echo "FAIL: resumed observable series diverges from uninterrupted run" >&2
    exit 1
fi
echo "kill-and-resume smoke OK (series bit-identical)"

echo
echo "=== CI tier 5: serving smoke (continuous batching, DESIGN.md §8) ==="
# Admit 3 sessions into the slot pool, NaN-bomb one mid-run via the
# attr-borne trigger (tests/faults.nan_bomb_attr_op — state, not structure,
# so all sessions share ONE compiled program), and assert: the sick session
# is evicted on its per-slot HealthReport, and the survivors' observable
# series hash bit-identically to solo runs of the same seeds.
python - <<'EOF'
import hashlib

import jax
import numpy as np

from tests import faults
from repro.core import behaviors
from repro.core.api import Simulation
from repro.launch.abm_serve import SessionRequest, serve

def sha(obs):
    h = hashlib.sha256()
    for name in sorted(obs):
        h.update(name.encode())
        h.update(np.ascontiguousarray(np.asarray(obs[name])).tobytes())
    return h.hexdigest()

rng = np.random.default_rng(6)
built = (
    Simulation(space=20.0, cell_size=4.0, boundary="toroidal", dt=1.0,
               capacity=16, max_per_cell=8, sort_frequency=4, seed=0)
    .add_agents(position=rng.uniform(0, 20, (16, 3)), diameter=1.0,
                nan_bomb_at=np.full(16, 2**30, np.int32))
    .use(behaviors.random_movement(1.0))
    .observe_kinds(n_kinds=2, frequency=2)
    .op(faults.nan_bomb_attr_op(), name="nan_bomb", phase="post")
    .build()
)
requests = [
    SessionRequest(name="clean0", n_steps=12, seed=21),
    SessionRequest(name="sick", n_steps=12, seed=22,
                   params={"attr:nan_bomb_at": np.int32(3)}),
    SessionRequest(name="clean1", n_steps=12, seed=23),
]
results = {r.name: r for r in serve(built, requests, slots=3, chunk=4)}
assert results["sick"].status == "evicted", results["sick"]
assert results["sick"].health["nonfinite_agents"] >= 1
for name, seed in (("clean0", 21), ("clean1", 23)):
    r = results[name]
    assert r.status == "done" and r.steps == 12, (name, r.status, r.steps)
    solo_state = built.batched().session_state(seed=seed)
    _, solo_obs = built.run_jit(12, state=solo_state)
    got, want = sha(r.obs), sha(solo_obs)
    print(f"{name}: served sha256={got[:16]} solo sha256={want[:16]}")
    assert got == want, f"{name} served series diverged from solo run"
print("serving smoke OK (NaN session evicted; survivors bit-identical)")
EOF

echo
echo "=== CI tier 6: overlapped-halo smoke (serial/overlap hash equality) ==="
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python tests/dist_scenarios.py overlap_smoke8

echo
echo "CI gate passed."
