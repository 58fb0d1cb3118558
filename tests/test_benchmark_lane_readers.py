"""Tier-1 runs the benchmark's own tests: the cases of
``benchmark/tests/test_lane_readers.py``, which stays where it is
(``pytest benchmark/tests`` runs them too)."""

from benchmark.tests.test_lane_readers import *  # noqa: F401,F403
