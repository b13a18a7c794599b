"""The rehearsal of the q/k/v-bias family, added as files only, under tier 1
(under a minute; see ``test_benchmark_harness.py``)."""

from benchmark.tests.test_rehearsal import (  # noqa: F401
    test_a_family_the_first_builder_refused_runs_as_files_only,
)
