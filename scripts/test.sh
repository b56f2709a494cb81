#!/usr/bin/env bash
# Tier-1 test entry point (see ROADMAP.md).
#
# Sets PYTHONPATH=src, pins JAX to the CPU and forces 8 host-platform
# devices (SNIPPETS.md idiom) so the multi-device launch/sharding paths are
# exercisable from one CPU process.  tests/conftest.py notes the unit tests must also pass on the
# real single device — CI should run both; this script is the multi-device
# flavor.  Extra args are forwarded to pytest.
#
# Companion: scripts/bench.sh is the benchmark smoke tier — every
# benchmarks/run.py target at shrunk sizes, so benchmark bit-rot fails fast.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
# CPU tests: on a machine with a TPU, the pytest parent would otherwise hold
# the chip and the multi-device subprocess tests would fail on libtpu's lock.
export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8${XLA_FLAGS:+ $XLA_FLAGS}"

exec python -m pytest -x -q "$@"
