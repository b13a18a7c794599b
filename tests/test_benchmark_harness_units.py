"""The benchmark's quick unit tests under tier 1: file discovery by name, the
trace reduction against its recorded fixture, the traffic generator, the
families' readers (see ``test_benchmark_harness.py``). The state-space readers
come by name: their test of a step's bytes shares its name with the window
family's, and a second star import would keep the later of the two only
(``test_benchmark_harness_collection.py`` holds both rules)."""

from benchmark.tests.test_discovery import *  # noqa: F401,F403
from benchmark.tests.test_gdn_readers import *  # noqa: F401,F403
from benchmark.tests.test_lmoe_readers import *  # noqa: F401,F403
from benchmark.tests.test_mla_long_readers import *  # noqa: F401,F403
from benchmark.tests.test_moe_prefill_chunk_ms import (  # noqa: F401
    test_the_chunk_group_reader_divides_whole_launches_only,
)
from benchmark.tests.test_reduce_trace import *  # noqa: F401,F403
from benchmark.tests.test_sala_readers import *  # noqa: F401,F403
from benchmark.tests.test_ssm_readers import (  # noqa: F401
    test_the_bytes_of_a_step_at_the_published_widths as test_the_bytes_of_a_state_space_step_at_the_published_widths,
    test_the_counters_readers_sum_the_windows_own_bursts,
    test_the_roofline_readers_divide_rows_and_seconds_of_the_same_span,
    test_the_trace_readers_divide_whole_launches_and_the_kernels_own_events,
)
from benchmark.tests.test_swa_readers import *  # noqa: F401,F403
from benchmark.tests.test_traffic import *  # noqa: F401,F403


def test_every_new_metric_is_in_the_manifest_for_the_new_cell_alone():  # noqa: F811
    """``benchmark/tests/test_swa_readers.py``'s test of this name holds the
    six entries of PR 37 as their readers declare them, for the new cell
    alone, and that they are the LAST of ``per_layer``: true when PR 37
    appended them, and false once any later PR appends its own, as the
    benchmark's contract has every PR do (a new entry goes to the end of its
    list; one put in the middle reads as a change to what was there, which
    only a ``benchmark`` PR may make, and that file is such a PR's to edit
    too). So tier 1 holds the same entries at the place where PR 37 left
    them, which appending never moves."""
    import json

    from benchmark.tests import test_swa_readers as swa

    man = json.loads((swa.BENCH.parent / "BENCHMARK.json").read_text())
    by_name = {m["name"]: m for m in man["per_layer"]}
    for name in swa.NAMES:
        declared = dict(swa.reader(name).METRIC, workloads=["lagunaxs2.code_closed"])
        assert by_name[name] == declared, name
    accepted = 33   # entries of ``per_layer`` when PR 37 was accepted, its six the last
    at = slice(accepted - len(swa.NAMES), accepted)
    assert [m["name"] for m in man["per_layer"][at]] == list(swa.NAMES)
