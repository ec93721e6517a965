"""bench_chip's own tests: run by hand, `JAX_PLATFORMS=cpu python -m pytest bench_chip/tests -q`.
They are not part of tests/ (the driver's tier-1 run does not collect them)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
