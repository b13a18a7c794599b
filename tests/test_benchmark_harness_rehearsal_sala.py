"""The rehearsal of the lightning / block-sparse family (Lightning linear
attention beside attention over picked blocks of keys), added as files only,
under tier 1: to a ``correct`` line, and to a not-``correct`` line with a fault
(about a minute each; see ``test_benchmark_harness.py``)."""

from benchmark.tests.test_rehearsal_sala import (  # noqa: F401
    test_a_fault_in_the_state_step_comes_out_not_correct,
    test_the_lightning_family_runs_as_files_only_to_a_correct_line,
)
