"""Tier-1 runs the benchmark's own tests: the cases of
``benchmark/tests/test_delivery_encode_share.py`` (PR 37: the reader of the
delivery sweep's encode count), which stays where it is (``pytest
benchmark/tests`` runs them too)."""

from benchmark.tests.test_delivery_encode_share import *  # noqa: F401,F403
