"""The state-space family's bytes from shapes and its five readers on
hand-made sources with known answers (``lib/roofline_ssm_hybrid.py``,
``layer_metrics/ssm_*.py``): the arithmetic at the published widths, and a
source that lacks what a reader reads (a parent commit, a dense
configuration, an untraced run) gives ``None`` and never raises."""

import json

import pytest

from benchmark import run
from benchmark.lib import roofline_ssm_hybrid as rl
from benchmark.tests.test_reduce_trace import FIX, MS

BENCH = FIX.parent
CONF = json.loads((BENCH / "configs" / "granite-4.0-h-micro.json").read_text())
DENSE = json.loads((BENCH / "configs" / "granite-3.1-8b.json").read_text())
DEVICE = {"kind": "TPU v5 lite", "count": 1}


def reader(name):
    return run.load_module(BENCH / "layer_metrics" / f"{name}.py")


def test_the_bytes_of_a_step_at_the_published_widths():
    assert rl.kinds(CONF) == (36, 4)
    # 36 x 76.18 M + 4 x 60.82 M parameters in the layers, the final norm and
    # the head (the embedding table is read by rows): 6.38 GB in bf16
    assert rl.weight_bytes(CONF) == pytest.approx(6.383e9, rel=2e-3)
    assert rl.state_layer_bytes(CONF) == 64 * 64 * 128 * 4          # 2.10 MB a slot a layer
    assert rl.tail_layer_bytes(CONF) == 4 * 4352 * 2
    assert rl.kv_token_bytes(CONF) == 2 * 8 * 64 * 2                # K and V, 8 kv heads of 64
    # 27 live rows: their state in and out is 4.1 GB beside the weights
    need = rl.decode_step_bytes(CONF, 27, 27 * 600)
    assert need - rl.weight_bytes(CONF) == pytest.approx(
        2 * 27 * 36 * (2097152 + 34816) + 4 * 27 * 600 * 2048 + 2 * 27 * 2048)
    assert rl.state_step_call_bytes(CONF, 27) == 2 * 27 * 2097152
    assert rl.state_step_call_ops(CONF, 27) == 4 * 27 * 64 * 64 * 128


def spans(bursts):
    """``batcher.readback`` records of decode bursts: (t1, rows, steps)."""
    return [("batcher.readback", t1 - 0.01, t1, {"program": "decode", "state_rows": r * s,
                                                  "state_steps": s}) for t1, r, s in bursts]


def test_the_counters_readers_sum_the_windows_own_bursts():
    src = {"window": (10.0, 20.0), "spans": spans([(9.5, 32, 8), (12.0, 24, 8), (15.0, 30, 8),
                                                   (21.0, 1, 8)])
           + [("batcher.readback", 13.0, 13.1, {"program": "admit"})],
           "samples": [{"pool": {"state": {"slots_live": 24, "slots_total": 32}}},
                       {"pool": {"state": {"slots_live": 32, "slots_total": 32}}},
                       {"pool": None}]}
    assert reader("ssm_rows_live_avg").read(src) == pytest.approx(27.0)
    assert reader("ssm_state_pool_used_share").read(src) == pytest.approx(87.5)
    bare = {"window": (10.0, 20.0), "spans": [], "samples": [{"pool": {"blocks_live": 3}}]}
    assert reader("ssm_rows_live_avg").read(bare) is None
    assert reader("ssm_state_pool_used_share").read(bare) is None


def test_the_trace_readers_divide_whole_launches_and_the_kernels_own_events():
    planes = {"/device:TPU:0": {
        "XLA Modules": [("jit_decode_pos_pallas(1)", 0, 100 * MS),        # first: left out
                        ("jit_decode_pos_pallas(1)", 100 * MS, 160 * MS),
                        ("jit_prefill_chunk_group(2)", 260 * MS, 30 * MS),
                        ("jit_admit_many_fused_paged(3)", 290 * MS, 10 * MS),
                        ("jit_decode_pos_pallas(1)", 300 * MS, 160 * MS),
                        ("jit_decode_pos_pallas(1)", 460 * MS, 100 * MS)],  # last: left out
        "XLA Ops": [("%ssm_state_step.7 = f32[32,36,32,128,128]{4,3,2,1,0} custom-call(...)", 110 * MS,
                     200_000),
                    ("%ssm_state_step.8 = f32[32,36,32,128,128]{4,3,2,1,0} custom-call(...)", 120 * MS,
                     240_000),
                    ("%paged_decode_attention.3 = bf16[32,1,32,128]{3,2,1,0} custom-call(...)", 130 * MS,
                     90_000)]}}
    from benchmark.lib import reduce_trace as rt

    src = {"config": CONF, "device": DEVICE, "planes": planes, "trace": rt.reduce(planes),
           "engine": {"decode_burst": 8}, "window": (10.0, 20.0), "spans": spans([(15.0, 27, 8)]),
           "samples": [{"pool": {"blocks_live": 1000, "block_tokens": 16}}]}
    # a step is 160 ms / 8 = 20 ms of the two whole launches
    need = rl.decode_step_bytes(CONF, 27.0, 16000.0)
    assert reader("ssm_decode_step_roofline").read(src) == pytest.approx(
        100.0 * need / 819e9 / 0.020)
    # (30 + 10) ms over the two prefill launches
    assert reader("ssm_prefill_chunk_ms").read(src) == pytest.approx(20.0)
    # a call is 220 us on average; 27 rows' state of a layer in and out is 113 MB
    assert reader("ssm_state_step_roofline").read(src) == pytest.approx(
        100.0 * 2 * 27 * 2097152 / 819e9 / 220e-6)
    for name in ("ssm_decode_step_roofline", "ssm_prefill_chunk_ms", "ssm_state_step_roofline"):
        assert reader(name).read(dict(src, config=DENSE)) is None   # another family's cell
        assert reader(name).read(dict(src, planes={}, trace={"device_planes": 0})) is None


def test_the_roofline_readers_divide_rows_and_seconds_of_the_same_span():
    """Since PR 36 the state kernel's time follows the live rows, so a share
    prices the traced span's own bursts: the window's mean over the span's
    seconds read 87 % where the span's rows give 79 % (PERF.md, PR 36)."""
    planes = {"/device:TPU:0": {
        "XLA Modules": [("jit_decode_pos_pallas(1)", s * MS, 130 * MS) for s in (0, 130, 260, 390)],
        "XLA Ops": [("%ssm_state_step.6 = f32[32,36,32,128,128]{4,3,2,1,0} custom-call(...)",
                     140 * MS, 141_900)]}}
    from benchmark.lib import reduce_trace as rt

    # a window of 30 s at 24.2 live rows; the bursts read back inside the
    # traced span [23, 27) held 21.9, and one more ends on its far edge
    bursts = [(12.0, 25, 8), (18.0, 26, 8), (24.0, 22, 8), (26.0, 21.8, 8), (27.0, 26, 8),
              (35.0, 24.4, 8)]
    src = {"config": CONF, "device": DEVICE, "planes": planes, "trace": rt.reduce(planes),
           "engine": {"decode_burst": 8}, "window": (10.0, 40.0), "spans": spans(bursts),
           "samples": [{"pool": {"blocks_live": 1000, "block_tokens": 16}}]}
    assert rl.live_rows(src) == pytest.approx(24.2)
    assert rl.span_live_rows(src) == pytest.approx(21.9)
    assert reader("ssm_rows_live_avg").read(src) == pytest.approx(24.2)    # the window's, as before
    new = reader("ssm_state_step_roofline").read(src)
    assert new == pytest.approx(100.0 * 2 * 21.9 * 2097152 / 819e9 / 141.9e-6)
    assert new == pytest.approx(79.0, abs=0.1) and new * 24.2 / 21.9 == pytest.approx(87.3, abs=0.1)
    assert reader("ssm_decode_step_roofline").read(src) == pytest.approx(
        100.0 * rl.decode_step_bytes(CONF, 21.9, 16000.0) / 819e9 / (0.130 / 8))
    # the span as run.py started and stopped the profiler, where it says so
    assert rl.span_live_rows(dict(src, span=(17.5, 18.5))) == pytest.approx(26.0)
    assert rl.span_live_rows(dict(src, span=(None, None))) == pytest.approx(21.9)
    # no burst read back inside the span: nothing to price a time against
    for name in ("ssm_state_step_roofline", "ssm_decode_step_roofline"):
        assert reader(name).read(dict(src, spans=spans(bursts[:2]))) is None
