"""The rehearsal of the plain latent-attention family (one residual stream,
one query matrix, two shared experts), added as files only, under tier 1
(about a minute; see ``test_benchmark_harness.py``)."""

from benchmark.tests.test_rehearsal_mla_plain import (  # noqa: F401
    test_the_plain_latent_family_runs_as_files_only_to_a_correct_line,
)
