"""The harness's CPU tests: ``python -m pytest bench/tests -q`` from the
root of a checkout, with ``JAX_PLATFORMS=cpu``."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
