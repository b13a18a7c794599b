"""``benchmark/tests/test_reference.py`` under tier 1 (see
``test_benchmark_harness.py``)."""

from benchmark.tests.test_reference import *  # noqa: F401,F403
