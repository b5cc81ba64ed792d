"""Tests of the benchmark's own code. CPU, seconds. Run with
`python -m pytest benchmarks/tests -q` from the checkout's root."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
CHECKOUT = os.path.dirname(BENCH)
for p in (BENCH, CHECKOUT):
    if p not in sys.path:
        sys.path.insert(0, p)
