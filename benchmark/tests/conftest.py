"""Tests of the yardstick itself: `pytest benchmark/tests` (CPU, by
hand; the repo's tier-1 run does not collect this directory)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]
