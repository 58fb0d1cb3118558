"""Tier-1 runs the benchmark's own tests: the cases of
``benchmark/tests/test_gc_pause_ms.py``, which stays where it is
(``pytest benchmark/tests`` runs them too)."""

from benchmark.tests.test_gc_pause_ms import *  # noqa: F401,F403
