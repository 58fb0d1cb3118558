"""Tier-1 runs the benchmark's CPU rehearsal of the ``ws_table`` kind
(PR 38): the comparison-must-fail cases, the stall across the window's
end, the early refusal and the readers' own cases of
``benchmark/tests/test_table.py``, which stays where it is."""

from benchmark.tests.test_table import (  # noqa: F401
    test_a_cut_or_stale_grid_is_not_correct,
    test_a_program_without_matrix_channels_is_refused_at_once,
    test_a_stall_across_the_windows_end_leaves_the_table_run_correct,
    test_matrix_counts_snapshot_names_what_the_program_counts,
    test_table_readers_give_numbers_from_the_counts,
    test_table_readers_read_nothing_without_the_counts,
)
