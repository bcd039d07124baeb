"""The benchmark's own tests, on the CPU: ``python -m pytest bench/tests``
from the root of a checkout."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src"), str(HERE)]
