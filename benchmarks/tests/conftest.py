"""``python -m pytest benchmarks/tests``: the yardstick's own tests, on the
CPU. Not part of the repository's tier-1 suite."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.dirname(BENCH_DIR), BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)
