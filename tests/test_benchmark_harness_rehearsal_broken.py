"""The two rehearsals with the timed path broken underneath, under tier 1
(half a minute each; see ``test_benchmark_harness.py``)."""

from benchmark.tests.test_rehearsal import (  # noqa: F401
    test_a_broken_timed_path_comes_out_not_correct,
)
