"""The benchmark's own tests run on the CPU with four virtual devices. They
live with the benchmark and are not part of the repository's tier-1 suite
(``pytest tests/``): run them with ``python -m pytest benchmarks/tests -q``."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.dirname(BENCH_DIR), BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)
