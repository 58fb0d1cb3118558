"""Tier-1 runs the benchmark's CPU rehearsal: the end-to-end cells of
``benchmark/tests/test_rehearsal.py``, which stays where it is. Its 19
cases each start the server and the traffic in a child process; they are
spread over four modules so that no xdist worker (``--dist loadfile``)
carries them all."""

from benchmark.tests.test_rehearsal import (  # noqa: F401
    test_cell_runs_end_to_end,
    test_listed_workload_refuses_the_cpu,
)
