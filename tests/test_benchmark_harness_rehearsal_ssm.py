"""The rehearsal of the state-space / attention hybrid family, added as files
only, under tier 1 (about a minute; see ``test_benchmark_harness.py``)."""

from benchmark.tests.test_rehearsal_ssm import (  # noqa: F401
    test_the_state_space_family_runs_as_files_only_to_a_correct_line,
)
