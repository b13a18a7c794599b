"""The readers of the scopes inside the jitted programs
(``benchmark/lib/scopes.py``, ``benchmark/layer_metrics/decode_*_ms_per_step``,
``prefill_*``) on their recorded fixture, under tier 1 (see
``test_benchmark_harness_units.py``)."""

from benchmark.tests.test_scope_readers import *  # noqa: F401,F403
