"""Tier-1 runs the benchmark's CPU rehearsal of the ``ws_meeting`` kind
(PR 36): the end-to-end cases of ``benchmark/tests/test_meeting.py``, which
stays where it is. Each case starts the server and 240 websocket writers in
child processes; they are spread over two modules so that no xdist worker
(``--dist loadfile``) carries them all."""

from benchmark.tests.test_meeting import (  # noqa: F401
    test_a_stall_across_the_windows_end_leaves_the_run_correct,
    test_meeting_runs_end_to_end,
)
