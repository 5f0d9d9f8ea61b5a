"""The benchmark's own tests: on the CPU, at small sizes.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests

Four virtual CPU devices stand in for the four chips of the sharded
cell; the flag has to be set before JAX starts.
"""
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]
