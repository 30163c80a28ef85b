"""Tests of the benchmark's own files. Run from the repo's root:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider

They are not among the repo's tier-1 tests (`tests/`)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (os.path.join(BENCH, "reference"), BENCH, os.path.dirname(BENCH)):
    if path not in sys.path:
        sys.path.insert(0, path)
