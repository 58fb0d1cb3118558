"""Tier-1 runs the benchmark's CPU rehearsal of the ``ws_table`` kind
(PR 38): the end-to-end cases of ``benchmark/tests/test_table.py`` (the
traced run among them: this is the one file that traces
``rehearsal-table``) and its controls, which stay where they are. Each case
starts the server and 16 websocket writers in child processes; they are
spread over two modules so that no xdist worker (``--dist loadfile``)
carries them all."""

from benchmark.tests.test_table import (  # noqa: F401
    test_table_controls_are_told_apart,
    test_table_runs_end_to_end,
)
