"""Tier-1 runs the benchmark's CPU rehearsal: the back-pressure cases
(shed reads, throttled frames) of ``benchmark/tests/test_rehearsal.py``,
which stays where it is. Its 19
cases each start the server and the traffic in a child process; they are
spread over four modules so that no xdist worker (``--dist loadfile``)
carries them all."""

from benchmark.tests.test_rehearsal import (  # noqa: F401
    test_shed_reads_are_offered_again_and_do_not_fail,
    test_throttled_frames_are_offered_again_and_do_not_fail,
)
