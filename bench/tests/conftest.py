"""The benchmark's own tests: outside the repository's tier-1 test paths.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They run the harness on the CPU at tiny sizes (Pallas in interpret mode).
"""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
