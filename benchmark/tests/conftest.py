"""These tests are the benchmark's own (run by hand: `python3 -m pytest
benchmark/tests -q`); they are not part of the repo's tier-1 suite. They
run on the CPU asked for by name, with four virtual devices for the mesh
cell."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
