"""The window / full attention family's bytes from shapes and its six readers
on hand-made sources with known answers (``lib/roofline_swa_moe.py``,
``layer_metrics/swa_*.py``): the arithmetic at the published widths, the
traced span's own bursts and not the window's mean, and a source that lacks
what a reader reads (a parent commit, another family's configuration, an
untraced run) gives ``None`` and never raises."""

import json

import pytest

from benchmark import run
from benchmark.lib import roofline_swa_moe as rl
from benchmark.tests.test_reduce_trace import FIX, MS

BENCH = FIX.parent
CONF = json.loads((BENCH / "configs" / "laguna-xs.2.json").read_text())
DENSE = json.loads((BENCH / "configs" / "granite-3.1-8b.json").read_text())
XING = json.loads((BENCH / "configs" / "xing4.0-29b-a4b.json").read_text())
DEVICE = {"kind": "TPU v5 lite", "count": 1}
NAMES = ("swa_decode_step_roofline", "swa_window_kernel_roofline", "swa_full_kernel_roofline",
         "swa_prefill_chunk_ms", "swa_kv_window_share", "swa_experts_hit_avg")


def reader(name):
    return run.load_module(BENCH / "layer_metrics" / f"{name}.py")


def test_the_bytes_of_a_step_at_the_published_widths():
    assert rl.is_family(CONF) and not rl.is_family(DENSE) and not rl.is_family(XING)
    assert rl.kinds(CONF) == (3, 2) and rl.moe_layers(CONF) == 4
    assert rl.kv_token_bytes(CONF) == 2 * 2 * 8 * 128               # 4,096 B a token a layer
    assert rl.expert_bytes(CONF) == 2 * 3 * 2048 * 512              # 6.29 MB
    # ISSUE 37's table less the experts and the embedding table (read by rows):
    # 2 full + 3 window attentions, the dense MLP, 4 routers + shared experts,
    # the head: 0.89 GB
    params = (3_869_856_768 - 4 * 256 * 3 * 2048 * 512 - 100352 * 2048)
    assert rl.non_expert_weight_bytes(CONF) == pytest.approx(2 * params, rel=1e-4)
    assert 0.88e9 < rl.non_expert_weight_bytes(CONF) < 0.90e9
    # 13 rows at 9,000 tokens: 0.96 GB in the full layers, 0.08 GB in the rings
    need = rl.decode_step_bytes(CONF, 20.0, 13 * 9000, 13 * 512, 13)
    assert need - rl.non_expert_weight_bytes(CONF) == pytest.approx(
        4 * 20 * 6291456 + 2 * 13 * 9000 * 4096 + 3 * 13 * 512 * 4096 + 2 * 13 * 2048)
    assert rl.kernel_call_bytes(CONF, 13 * 512) == 13 * 512 * 4096


def spans(bursts):
    """``batcher.readback`` records of decode bursts of 8 steps: (t1, rows,
    context at the burst's start, experts hit a step and layer)."""
    out = []
    for t1, rows, ctx, hit in bursts:
        full = sum(ctx + j + 1 for j in range(8)) * rows
        out.append(("batcher.readback", t1 - 0.01, t1, {
            "program": "decode", "win_tokens": 8 * rows * 512, "full_tokens": full,
            "win_steps": 8, "experts_hit": hit * 8 * 4, "expert_rows_max": 8 * 4 * rows,
            "expert_rows": 8 * 4 * rows, "expert_steps": 8 * 4}))
    return out


def test_the_counter_readers_sum_the_windows_own_bursts():
    src = {"config": CONF, "window": (10.0, 40.0),
           "spans": spans([(9.5, 16, 100, 50), (12.0, 12, 4000, 20), (25.0, 14, 8000, 24),
                           (41.0, 1, 1, 1)])
           + [("batcher.readback", 13.0, 13.1, {"program": "admit"})]}
    full = 12 * sum(4001 + j for j in range(8)) + 14 * sum(8001 + j for j in range(8))
    win = 8 * 26 * 512
    assert reader("swa_kv_window_share").read(src) == pytest.approx(100.0 * win / (win + full))
    assert reader("swa_experts_hit_avg").read(src) == pytest.approx(22.0)
    bare = {"config": CONF, "window": (10.0, 40.0), "spans": []}
    assert reader("swa_kv_window_share").read(bare) is None
    assert reader("swa_experts_hit_avg").read(bare) is None
    # the expert counters of another family's cell are not this metric's
    assert reader("swa_experts_hit_avg").read(dict(src, config=XING)) is None


def test_the_trace_readers_price_the_traced_spans_own_bursts():
    planes = {"/device:TPU:0": {
        "XLA Modules": [("jit_decode_pos_moe(1)", 0, 50 * MS),             # first: left out
                        ("jit_decode_pos_moe(1)", 100 * MS, 40 * MS),
                        ("jit_prefill_chunk_group(2)", 260 * MS, 30 * MS),
                        ("jit_prefill1(3)", 290 * MS, 10 * MS),
                        ("jit_decode_pos_moe(1)", 300 * MS, 40 * MS),
                        ("jit_decode_pos_moe(1)", 460 * MS, 50 * MS)],     # last: left out
        "XLA Ops": [("%window_decode_attention.7 = bf16[16,8,16,128]{3,2,1,0} custom-call(...)",
                     110 * MS, 60_000),
                    ("%window_decode_attention.8 = bf16[16,8,16,128]{3,2,1,0} custom-call(...)",
                     120 * MS, 80_000),
                    ("%paged_decode_attention.3 = bf16[16,8,16,128]{3,2,1,0} custom-call(...)",
                     130 * MS, 600_000)]}}
    from benchmark.lib import reduce_trace as rt

    # a window of 30 s: the traced span is [23, 27); only the burst read back
    # at 25.0 lies in it (14 rows at 8,000, 24 experts hit), the window's
    # other bursts (12 rows at 4,000; 16 at 100) must not be priced
    src = {"config": CONF, "device": DEVICE, "planes": planes, "trace": rt.reduce(planes),
           "engine": {"decode_burst": 8}, "window": (10.0, 40.0),
           "spans": spans([(12.0, 12, 4000, 20), (25.0, 14, 8000, 24), (30.0, 16, 100, 50)])}
    assert rl.traced_span(src) == (23.0, 27.0)
    full = 14 * sum(8001 + j for j in range(8)) / 8
    need = rl.decode_step_bytes(CONF, 24.0, full, 14 * 512, 14)
    # a step is 40 ms / 8 = 5 ms of the two whole launches
    assert reader("swa_decode_step_roofline").read(src) == pytest.approx(
        100.0 * need / 819e9 / 0.005)
    assert reader("swa_window_kernel_roofline").read(src) == pytest.approx(
        100.0 * 14 * 512 * 4096 / 819e9 / 70e-6)
    assert reader("swa_full_kernel_roofline").read(src) == pytest.approx(
        100.0 * full * 4096 / 819e9 / 600e-6)
    assert reader("swa_prefill_chunk_ms").read(src) == pytest.approx(20.0)   # (30 + 10) / 2
    for name in NAMES[:4]:
        assert reader(name).read(dict(src, config=DENSE)) is None   # another family's cell
        assert reader(name).read(dict(src, planes={}, trace={"device_planes": 0})) is None
    # no burst read back inside the traced span: nothing to price a time against
    for name in NAMES[:3]:
        assert reader(name).read(dict(src, spans=spans([(12.0, 12, 4000, 20)]))) is None


def test_every_new_metric_is_in_the_manifest_for_the_new_cell_alone():
    man = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    by_name = {m["name"]: m for m in man["per_layer"]}
    for name in NAMES:
        declared = dict(reader(name).METRIC, workloads=["lagunaxs2.code_closed"])
        assert by_name[name] == declared, name
    assert [m["name"] for m in man["per_layer"][-len(NAMES):]] == list(NAMES)


def test_what_this_pr_adds_to_the_manifest_is_inside_the_contracts_limits():
    """The limits a manifest is refused over before any run: a name's
    characters and length, a ``why`` of at most 200 characters on one line,
    the keys of an entry, the file's size."""
    import re

    path = BENCH.parent / "BENCHMARK.json"
    man = json.loads(path.read_text())
    assert path.stat().st_size <= 64 * 1024
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    conf = next(c for c in man["configs"] if c["name"] == "laguna-xs.2")
    cell = next(w for w in man["workloads"] if w["name"] == "lagunaxs2.code_closed")
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    assert set(cell) == {"name", "config", "traffic", "chips", "why"} and cell["chips"] == 1
    for text in (conf["why"], cell["why"], conf["source"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text, (len(text), text)
    for word in [conf["name"], cell["name"], cell["config"], cell["traffic"], *conf["reduced"], *NAMES]:
        assert name.match(word), word
    assert (BENCH.parent / conf["file"]).is_file() and conf["file"].startswith("benchmark/")
    assert (BENCH / "traffic" / f"{cell['traffic']}.json").is_file()
    # every key of `reduced` is a key of the file, and none of them is a width
    assert set(conf["reduced"]) <= set(CONF)
    assert not [k for k in conf["reduced"] if k.endswith(("_dim", "_rank", "_size"))]
