"""Tier-1 runs the benchmark's own tests: the cases of
``benchmark/tests/test_merge_apply_roofline.py``, which stays where it is
(``pytest benchmark/tests`` runs them too)."""

from benchmark.tests.test_merge_apply_roofline import *  # noqa: F401,F403
