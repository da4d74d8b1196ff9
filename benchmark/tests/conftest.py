"""The benchmark's own tests: run by hand, not part of tier-1.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
