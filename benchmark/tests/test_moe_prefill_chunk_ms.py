"""The reader of ``moe_prefill_chunk_ms`` on a hand-made trace with known
answers, on the small trace recorded on the chip, and on a line without a
trace (``layer_metrics/moe_prefill_chunk_ms.py``)."""

import json

import pytest

from benchmark.lib import reduce_trace as rt
from benchmark.tests.test_reduce_trace import FIX, HAND, MS

CHUNKS = {"/device:TPU:0": {"XLA Modules": [
    ("jit_prefill_chunk_group(5)", -10 * MS, 30 * MS),   # cut by the span's start: not counted
    ("jit_prefill_chunk_group(5)", 30 * MS, 40 * MS),
    ("jit_decode_pos_moe(6)", 70 * MS, 10 * MS),
    ("jit_prefill_chunk_group(7)", 80 * MS, 20 * MS),    # another shape of the same program
    ("jit_prefill1(8)", 100 * MS, 10 * MS),
    ("jit_prefill_chunk_group(5)", 180 * MS, 40 * MS),   # cut by the span's end
]}}


@pytest.mark.parametrize("planes,span_ms,want", [
    (CHUNKS, (0, 200), 30.0),     # (40 + 20) ms over the two launches wholly inside
    (CHUNKS, (90, 200), None),    # no whole launch of the program in the span
    (HAND, (0, 100), None),       # a trace that never ran it
    ("recorded", None, None),     # the chip's recorded trace holds one prefill1 and no group
])
def test_the_chunk_group_reader_divides_whole_launches_only(planes, span_ms, want):
    from benchmark import run

    reader = run.load_module(FIX.parent / "layer_metrics" / "moe_prefill_chunk_ms.py")
    if planes == "recorded":
        if not (FIX / "trace_planes.json").exists():
            pytest.skip("no recorded fixture")
        planes = {p: {ln: [tuple(e) for e in evs] for ln, evs in lines.items()}
                  for p, lines in json.loads((FIX / "trace_planes.json").read_text()).items()}
    span = None if span_ms is None else (span_ms[0] * MS, span_ms[1] * MS)
    got = reader.read({"trace": rt.reduce(planes, span_ns=span)})
    assert got == (want if want is None else pytest.approx(want))
    assert reader.read({"trace": {}}) is None    # a parent or a run without a trace: no raise
