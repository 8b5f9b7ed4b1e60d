"""The benchmark's self-check runs on the CPU: ``JAX_PLATFORMS=cpu
python -m pytest bench/tests``."""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(BENCH, "tools"))
