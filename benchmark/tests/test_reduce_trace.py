"""The trace reduction on a hand-made trace with known answers, and on the
small trace recorded on the chip (``benchmark/fixtures/``)."""

import json
from pathlib import Path

import pytest

from benchmark.lib import reduce_trace as rt

FIX = Path(__file__).resolve().parents[1] / "fixtures"

MS = 1_000_000
HAND = {
    "/device:TPU:0": {
        "XLA Modules": [("jit_decode_pos_pallas(123)", 0, 40 * MS),
                        ("jit_decode_pos_pallas(123)", 50 * MS, 40 * MS),
                        ("jit_admit_fused_paged(9)", 95 * MS, 5 * MS)],
        "XLA Ops": [("fusion.1", 0, 30 * MS), ("custom-call.2", 25 * MS, 15 * MS),
                    ("fusion.1", 50 * MS, 40 * MS), ("fusion.7", 95 * MS, 5 * MS)],
    },
    "/host:CPU": {
        "batcher/1": [("readback", 38 * MS, 14 * MS), ("serve", 0, 100 * MS)],
        "main/2": [("publish", 90 * MS, 4 * MS)],
    },
}


def test_hand_made_trace_reduces_to_known_numbers():
    out = rt.reduce(HAND)
    assert out["device_planes"] == 1
    assert out["window_s"] == pytest.approx(0.100)
    assert out["busy_s"] == pytest.approx(0.085)          # 0-40, 50-90, 95-100: overlap counted once
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(0.070)]
    progs = out["programs"]
    assert progs["decode_pos_pallas"] == {"launches": 2, "seconds": pytest.approx(0.080)}
    assert progs["admit_fused_paged"]["launches"] == 1
    gaps = dict(out["idle_gaps"])
    assert gaps["batcher:readback"] == pytest.approx(0.010)  # 40-50: the innermost span that covers it
    assert gaps["main:publish"] == pytest.approx(0.005)      # 90-95: publish covers 4 of its 5 ms
    assert out["longest_gap_s"] == pytest.approx(0.010)


def test_span_clips_and_planes_average():
    two = dict(HAND, **{"/device:TPU:1": {"XLA Ops": [("fusion.1", 0, 100 * MS)]}})
    out = rt.reduce(two, span_ns=(0, 100 * MS))
    assert out["device_planes"] == 2 and out["busy_s"] == pytest.approx((0.085 + 0.1) / 2)
    assert rt.reduce({"/host:CPU": {}}) == {"device_planes": 0}
    assert rt.program_name("jit_spec_verify_pallas(77)") == "spec_verify_pallas"


@pytest.mark.skipif(not (FIX / "trace_planes.json").exists(), reason="no recorded fixture")
def test_recorded_chip_trace_reduces_to_its_recorded_numbers():
    planes = json.loads((FIX / "trace_planes.json").read_text())
    planes = {p: {ln: [tuple(e) for e in evs] for ln, evs in lines.items()}
              for p, lines in planes.items()}
    want = json.loads((FIX / "trace_expected.json").read_text())
    got = rt.reduce(planes)
    assert got["device_planes"] == want["device_planes"]
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert [k for k, _ in got["device_ops"]] == [k for k, _ in want["device_ops"]]
    assert {k: v["launches"] for k, v in got["programs"].items()} == {
        k: v["launches"] for k, v in want["programs"].items()}
