"""The benchmark's own tests: ``python -m pytest benchmark/tests -q`` from the
root of the repo. Tier-1 collects only ``tests/``, so these never count
there."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
