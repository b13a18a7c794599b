"""The benchmark's quick unit tests under tier 1: file discovery by name, the
trace reduction against its recorded fixture, the traffic generator (see
``test_benchmark_harness.py``; no two of the three share a name)."""

from benchmark.tests.test_discovery import *  # noqa: F401,F403
from benchmark.tests.test_moe_prefill_chunk_ms import (  # noqa: F401
    test_the_chunk_group_reader_divides_whole_launches_only,
)
from benchmark.tests.test_reduce_trace import *  # noqa: F401,F403
from benchmark.tests.test_ssm_readers import *  # noqa: F401,F403
from benchmark.tests.test_swa_readers import *  # noqa: F401,F403
from benchmark.tests.test_traffic import *  # noqa: F401,F403
