"""The plain latent-attention family's bytes and operations from shapes and
its eight readers on hand-made sources with known answers
(``lib/roofline_mla_plain.py``, ``layer_metrics/mla_long_*.py``): the
arithmetic at the published widths against the program's own initialiser,
the traced span's own bursts and chunk launches and not the window's mean,
and a source that lacks what a reader reads (a parent commit, another
family's configuration, an untraced run) gives ``None`` and never raises."""

import json

import pytest

from benchmark import run
from benchmark.lib import roofline_mla_plain as rl
from benchmark.tests.test_reduce_trace import FIX, MS

BENCH = FIX.parent
CONF = json.loads((BENCH / "configs" / "kanana-2-30b-a3b-instruct-2601.json").read_text())
XING = json.loads((BENCH / "configs" / "xing4.0-29b-a4b.json").read_text())
LAGUNA = json.loads((BENCH / "configs" / "laguna-xs.2.json").read_text())
DEVICE = {"kind": "TPU v5 lite", "count": 1}
CELL = "kanana2.longctx_closed"
NAMES = ("mla_long_decode_step_roofline", "mla_long_kernel_roofline",
         "mla_long_kernel_ms_per_step", "mla_long_prefill_chunk_ms",
         "mla_long_prefill_attn_share", "mla_long_prefill_chunk_mfu",
         "mla_long_experts_hit_avg", "mla_long_rows_live_avg")


def reader(name):
    return run.load_module(BENCH / "layer_metrics" / f"{name}.py")


def test_mla_long_counts_are_the_programs_own_tree_at_the_published_widths():
    import math

    import jax

    assert rl.is_family(CONF) and not rl.is_family(XING) and not rl.is_family(LAGUNA)
    ref = run.load_module(BENCH / "references" / "mla_moe_plain.py")
    shapes = ref.param_shapes(ref.model_config(CONF, 32768))
    leaves = jax.tree.leaves(shapes)
    assert sum(math.prod(x.shape) for x in leaves) == rl.param_count(CONF) == 3_789_584_000
    assert all(x.dtype == "bfloat16" for x in leaves)
    assert rl.expert_bytes(CONF) == 2 * 4_718_592 and rl.cache_token_bytes(CONF) == 1152
    # what a step reads whole: the tree less the routed experts and the
    # embedding table (read by rows): 1.01 GB, 0.53 GB of it the head
    whole = 2 * (3_789_584_000 - 5 * 128 * 4_718_592 - 128256 * 2048)
    assert rl.non_expert_weight_bytes(CONF) == whole and 1.00e9 < whole < 1.03e9
    need = rl.decode_step_bytes(CONF, 40.0, 10 * 14000, 10)
    assert need - whole == 5 * 40 * 9_437_184 + 6 * 140_000 * 1152 + 2 * 10 * 2048
    assert rl.kernel_call_bytes(CONF, 140_000) == 140_000 * 1152
    # a chunk of 256 tokens of one row at start 10,240: its pairs, its own
    # expansion once, one head row
    pairs = 256 * 10240 + 256 * 257 // 2
    per_token = (6 * 26_345_472 + 37_748_736
                 + 5 * (2048 * 128 + (2 + 6) * 4_718_592))
    assert rl.chunk_min_flops(CONF, 1, 256, pairs) == pytest.approx(
        2.0 * (256 * per_token + pairs * 6 * 32 * 320 + 2048 * 128256))


def bursts(rows):
    """``batcher.readback`` records of decode bursts of 8 steps: (t1, live
    rows, context of each at the burst's start, experts hit a step and layer)."""
    return [("batcher.readback", t1 - 0.01, t1, {
        "program": "decode", "live_tokens": n * ctx, "experts_hit": hit * 8 * 5,
        "expert_rows_max": 8 * 5 * n, "expert_rows": 8 * 5 * n, "expert_steps": 8 * 5})
        for t1, n, ctx, hit in rows]


def chunk(t0, rows, tokens, start):
    real = [tokens // rows] * rows
    return ("batcher.admit", t0, t0 + 0.001, {
        "program": "chunk", "rows": rows, "tokens": tokens,
        "live_keys": sum(start + t for t in real),
        "pairs": sum(t * start + t * (t + 1) // 2 for t in real)})


def test_mla_long_counter_readers_sum_the_windows_own_bursts():
    src = {"config": CONF, "window": (10.0, 40.0),
           "spans": bursts([(9.5, 16, 100, 90), (12.0, 8, 9000, 30), (25.0, 12, 15000, 42),
                            (41.0, 1, 1, 1)])
           + [("batcher.readback", 13.0, 13.1, {"program": "admit"})]}
    assert reader("mla_long_experts_hit_avg").read(src) == pytest.approx(36.0)
    assert reader("mla_long_rows_live_avg").read(src) == pytest.approx(10.0)
    for name in NAMES[-2:]:
        assert reader(name).read({"config": CONF, "window": (10.0, 40.0), "spans": []}) is None
        assert reader(name).read(dict(src, config=XING)) is None   # another family's cell
        # a parent's bursts carry the expert counters and no ``live_tokens``
        old = [(n, a, b, {k: v for k, v in at.items() if k != "live_tokens"})
               for n, a, b, at in src["spans"]]
        assert reader(name).read(dict(src, spans=old)) is None


def test_mla_long_trace_readers_price_the_traced_spans_own_launches():
    from benchmark.lib import reduce_trace as rt

    kernel = "%mla_paged_decode_attention.{} = bf16[16,1,32,512]{{3,2,1,0}} custom-call(...)"
    planes = {"/device:TPU:0": {
        "XLA Modules": [("jit_decode_pos_moe(1)", 0, 50 * MS),             # first: left out
                        ("jit_decode_pos_moe(1)", 100 * MS, 48 * MS),
                        ("jit_prefill_chunk_group(2)", 200 * MS, 60 * MS),
                        ("jit_prefill1(3)", 270 * MS, 20 * MS),
                        ("jit_decode_pos_moe(1)", 300 * MS, 48 * MS),
                        ("jit_decode_pos_moe(1)", 460 * MS, 50 * MS)],     # last: left out
        "XLA Ops": [(kernel.format(7), 110 * MS, 300_000), (kernel.format(8), 120 * MS, 500_000)]}}
    # a window of 30 s: the traced span is [23, 27); only the burst read back
    # at 25.0 and the two launches that began in the span are priced
    src = {"config": CONF, "device": DEVICE, "planes": planes, "trace": rt.reduce(planes),
           "engine": {"decode_burst": 8}, "window": (10.0, 40.0),
           "spans": bursts([(12.0, 8, 9000, 30), (25.0, 12, 15000, 42), (30.0, 16, 100, 90)])
           + [chunk(11.0, 4, 1024, 20480), chunk(24.0, 3, 768, 8192),
              chunk(24.5, 1, 256, 4096), chunk(29.0, 2, 512, 0)]}
    assert rl.traced_span(src) == (23.0, 27.0)
    live = 12 * 15000 + 12 * (8 + 1) / 2       # a row reads its own new token too
    assert rl.step_means(CONF, rl.span_bursts(src)) == pytest.approx((42.0, 12.0, live))
    need = rl.decode_step_bytes(CONF, 42.0, live, 12)
    # a step is 48 ms / 8 = 6 ms of the two whole launches
    assert reader("mla_long_decode_step_roofline").read(src) == pytest.approx(
        100.0 * need / 819e9 / 0.006)
    assert reader("mla_long_kernel_roofline").read(src) == pytest.approx(
        100.0 * live * 1152 / 819e9 / 400e-6)
    assert reader("mla_long_kernel_ms_per_step").read(src) == pytest.approx(6 * 0.4)
    assert reader("mla_long_prefill_chunk_ms").read(src) == pytest.approx(40.0)   # (60 + 20) / 2
    pairs = 3 * (256 * 8192 + 256 * 257 // 2) + 256 * 4096 + 256 * 257 // 2
    flops = rl.chunk_min_flops(CONF, 4, 1024, pairs)     # the two launches of the span
    mfu = reader("mla_long_prefill_chunk_mfu").read(src)
    assert mfu == pytest.approx(100.0 * flops / 0.080 / 197e12) and 0 < mfu < 100
    # no scope vocabulary in a hand-made plane: nothing to read, never a raise
    assert reader("mla_long_prefill_attn_share").read(src) is None
    for name in NAMES[:6]:
        assert reader(name).read(dict(src, config=XING)) is None
        assert reader(name).read(dict(src, planes={}, trace={"device_planes": 0})) is None
    # no burst read back, no chunk begun inside the traced span: nothing to price
    bare = dict(src, spans=bursts([(12.0, 8, 9000, 30)]) + [chunk(11.0, 4, 1024, 0)])
    for name in ("mla_long_decode_step_roofline", "mla_long_kernel_roofline",
                 "mla_long_prefill_chunk_mfu"):
        assert reader(name).read(bare) is None


def test_mla_long_attn_share_is_the_chunk_launches_time_under_seq_mla(monkeypatch):
    from benchmark.lib import scopes

    table = {"launches": {"prefill_chunk_group": {"kind": "prefill", "n": 3, "ns": 90.0},
                          "prefill1": {"kind": "prefill", "n": 1, "ns": 10.0},
                          "decode_pos_moe": {"kind": "decode", "n": 5, "ns": 500.0}},
             "ops": {("prefill_chunk_group", "seq/mla"): [50.0, 9], ("prefill1", "seq/mla"): [6.0, 3],
                     ("prefill_chunk_group", "ffn/experts"): [30.0, 5],
                     ("prefill_chunk_group", None): [10.0, 2],
                     ("decode_pos_moe", "seq/mla"): [200.0, 40]}}
    monkeypatch.setattr(scopes, "table", lambda src: table)
    src = {"config": CONF}
    assert reader("mla_long_prefill_attn_share").read(src) == pytest.approx(56.0)
    monkeypatch.setattr(scopes, "table", lambda src: None)
    assert reader("mla_long_prefill_attn_share").read(src) is None


def test_mla_long_entries_are_in_the_manifest_for_the_new_cell_alone_and_inside_its_limits():
    import re

    path = BENCH.parent / "BENCHMARK.json"
    man = json.loads(path.read_text())
    assert path.stat().st_size <= 64 * 1024
    by_name = {m["name"]: m for m in man["per_layer"]}
    for name in NAMES:
        assert by_name[name] == dict(reader(name).METRIC, workloads=[CELL]), name
    ok = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    conf = next(c for c in man["configs"] if c["name"] == CONF["name"])
    cell = next(w for w in man["workloads"] if w["name"] == CELL)
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    assert set(cell) == {"name", "config", "traffic", "chips", "why"} and cell["chips"] == 1
    assert conf["reduced"] == CONF["reduced"] == ["num_hidden_layers"]
    for text in (conf["why"], cell["why"], conf["source"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    for n in (conf["name"], cell["name"], cell["traffic"], *NAMES):
        assert ok.match(n), n
    layers = {m["layer"] for m in man["per_layer"] if m["name"] not in NAMES}
    assert {by_name[n]["layer"] for n in NAMES} <= layers   # names the benchmark already has
    # the configuration's file: every number of the catalog's copy but the depth
    assert CONF["published"] == {"num_hidden_layers": 48} and CONF["num_hidden_layers"] == 6
    assert (CONF["n_routed_experts"], CONF["num_experts_per_tok"], CONF["n_shared_experts"],
            CONF["vocab_size"], CONF["hidden_size"], CONF["q_lora_rank"]) == (
        128, 6, 2, 128256, 2048, None)
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    assert (mix["callers"], mix["deck"], mix["order_seed"], mix["greedy_every"]) == (16, 32, 44, 4)
    assert mix["prompt_tokens"] == {"dist": "loguniform", "min": 4096, "max": 24576}
    assert mix["output_tokens"] == {"dist": "loguniform", "min": 256, "max": 1024}
