"""CPU-only tests of the benchmark's own yardstick.  Run by hand:

    python -m pytest benchmarks/tests

They are not part of the repo's tier-1 suite (which collects ``tests/``).
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
for p in (REPO, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
