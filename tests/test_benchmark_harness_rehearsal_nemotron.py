"""The rehearsal of the state-space family with a layer one sublayer (Mamba-2
of two groups, NoPE attention, a share of two-matrix relu^2 experts in a
latent), added as files only, under tier 1: to a ``correct`` line, and to a
not-``correct`` line with a fault (about a minute each; see
``test_benchmark_harness.py``)."""

from benchmark.tests.test_rehearsal_nemotron import (  # noqa: F401
    test_a_dropped_group_in_the_decode_step_comes_out_not_correct,
    test_the_latent_expert_family_runs_as_files_only_to_a_correct_line,
)
