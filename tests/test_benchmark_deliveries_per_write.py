"""Tier-1 runs the benchmark's own tests: the cases of
``benchmark/tests/test_deliveries_per_write.py`` (PR 45: the reader of the
delivery sweep's writes), which stays where it is (``pytest
benchmark/tests`` runs them too)."""

from benchmark.tests.test_deliveries_per_write import *  # noqa: F401,F403
