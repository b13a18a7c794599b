"""The rehearsal of the latent-attention / routed-expert / residual-stream
family, added as files only, under tier 1 (about a minute; see
``test_benchmark_harness.py``)."""

from benchmark.tests.test_rehearsal_mla import (  # noqa: F401
    test_the_family_runs_as_files_only_and_its_counters_come_back_with_the_tokens,
)
