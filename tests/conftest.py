"""Test configuration: force an 8-device virtual CPU mesh before JAX init.

Mirrors the reference's strategy of running the full pipeline in-process
(LocalDeltaConnectionServer); multi-chip sharding is validated on virtual CPU
devices; the chip is driven by chip_smoke.py, never by the tests.
"""

import os
import sys

# tests/test_benchmark_*.py import the benchmark's own cases from
# ``benchmark.tests`` (a namespace package of the repo root).
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
