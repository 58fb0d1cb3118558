"""Tier-1 runs the benchmark's own tests: the cases of
``benchmark/tests/test_trace_reduce.py``, which stays where it is
(``pytest benchmark/tests`` runs them too)."""

from benchmark.tests.test_trace_reduce import *  # noqa: F401,F403
