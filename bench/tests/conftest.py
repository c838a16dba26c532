"""Tests of the benchmark itself, on the CPU at small sizes.

    JAX_PLATFORMS=cpu python3 -m pytest bench/tests -q

Four virtual CPU devices stand in for the four-chip cell's mesh.
"""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
