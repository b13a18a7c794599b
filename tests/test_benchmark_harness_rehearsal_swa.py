"""The rehearsal of the window / full attention family, added as files only,
under tier 1 (about a minute; see ``test_benchmark_harness.py``)."""

from benchmark.tests.test_rehearsal_swa import (  # noqa: F401
    test_the_window_family_runs_as_files_only_to_a_correct_line,
)
