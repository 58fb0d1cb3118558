"""Tier-1 runs the benchmark's CPU rehearsal of the ``ws_meeting`` kind
(PR 36): the controls, the comparison-must-fail case, the early refusal and
the readers' own cases of ``benchmark/tests/test_meeting.py``, which stays
where it is."""

from benchmark.tests.test_meeting import (  # noqa: F401
    test_a_document_held_under_its_writers_in_slots_is_not_correct,
    test_a_program_with_a_narrower_cap_is_refused_at_once,
    test_frames_count_as_deliveries_beside_json_ops,
    test_meeting_controls_are_told_apart,
    test_meeting_counts_snapshot_names_what_the_program_counts,
    test_meeting_readers_give_numbers_from_the_counts,
    test_meeting_readers_read_nothing_without_the_counts,
    test_writer_slots_peak_is_what_the_kind_read_after_the_window,
)
