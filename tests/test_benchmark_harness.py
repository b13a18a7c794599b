"""Tier 1 collects ``tests/`` only, and the benchmark keeps its own tests
beside its code (``benchmark/tests/``, run by hand with ``python -m pytest
benchmark/tests``). This file and its ``test_benchmark_harness_*`` siblings
bring them under the count, a module (or a slice of the slow rehearsals) a
file so that ``--dist loadfile`` spreads them: here the decoded-position
check with its three breaks and the fp8 control. The rehearsals, each a whole
``benchmark/run.py`` in a subprocess, run side by side on as many workers as
draw them: a run keeps its scratch under ``benchmark/.cache/run-<pid>`` and
shares the compile cache alone. The tests themselves stay where they are; a
fixture resolves in the module that imports it, so no two benchmark modules
share a file unless their names do not collide, and
``test_benchmark_harness_collection.py`` holds that none is left out."""

from benchmark.tests.test_decode_check import *  # noqa: F401,F403
