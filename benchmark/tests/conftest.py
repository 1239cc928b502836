"""The benchmark's own tests run on the CPU, at sizes a test run can hold
(``python -m pytest benchmark/tests``; the repo's tier-1 command does
not collect them)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
