"""Tier-1 runs the benchmark's CPU rehearsal: the comparison-must-fail
cases of ``benchmark/tests/test_rehearsal.py``, which stays where it is. Its 19
cases each start the server and the traffic in a child process; they are
spread over four modules so that no xdist worker (``--dist loadfile``)
carries them all."""

from benchmark.tests.test_rehearsal import (  # noqa: F401
    test_altered_served_text_is_not_correct,
    test_answer_broken_where_it_is_produced_is_not_correct,
    test_rest_replies_broken_inside_the_window_are_not_correct,
)
