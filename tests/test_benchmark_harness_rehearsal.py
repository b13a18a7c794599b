"""The rehearsal of a whole sound run under tier 1: one run of the toy cell
(``sound``, half a minute) and the five tests that read it (see
``test_benchmark_harness.py``)."""

from benchmark.tests.test_rehearsal import (  # noqa: F401
    sound,
    test_every_phase_says_when_it_begins_and_where_the_run_stands,
    test_the_control_is_read_beside_the_reference_and_decides_nothing,
    test_the_result_line_says_where_the_window_started_and_what_closed_the_settle,
    test_the_sound_path_is_correct_through_the_live_batcher,
    test_what_was_compared_ends_standard_error,
)
