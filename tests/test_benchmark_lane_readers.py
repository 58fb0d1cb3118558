"""Tier-1 runs the benchmark's own tests: the cases of
``benchmark/tests/test_lane_readers.py`` and of
``benchmark/tests/test_deli_host_ms.py`` (PR 35: the deli lane's reader),
which stay where they are (``pytest benchmark/tests`` runs them too)."""

from benchmark.tests.test_lane_readers import *  # noqa: F401,F403
from benchmark.tests.test_deli_host_ms import *  # noqa: F401,F403
