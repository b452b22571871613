"""The benchmark's own tests run on the CPU: ``python -m pytest
benchmarks/tests -q`` from the root of the checkout."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for path in (ROOT, BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)
