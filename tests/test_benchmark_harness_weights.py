"""``benchmark/tests/test_weights.py`` under tier 1: the golden digests of the
seeded trees and the leaf-schema rules (see ``test_benchmark_harness.py``)."""

from benchmark.tests.test_weights import *  # noqa: F401,F403
