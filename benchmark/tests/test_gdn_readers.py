"""The linear-attention family's bytes and operations from shapes and its
eight readers on hand-made sources with known answers
(``lib/roofline_gdn_moe.py``, ``layer_metrics/gdn_*.py``): the arithmetic at
the published widths and the configuration's cut, and a source that lacks what
a reader reads (a parent commit, another family's configuration, an untraced
run) gives ``None`` and never raises."""

import json

import pytest

from benchmark import run
from benchmark.lib import roofline_gdn_moe as rl
from benchmark.tests.test_reduce_trace import FIX, MS

BENCH = FIX.parent
CONF = json.loads((BENCH / "configs" / "qwen3-next-80b-a3b-instruct.json").read_text())
OTHER = json.loads((BENCH / "configs" / "granite-4.0-h-micro.json").read_text())
DEVICE = {"kind": "TPU v5 lite", "count": 1}
NAMES = ("gdn_state_step_roofline", "gdn_decode_step_roofline", "gdn_seq_linear_share",
         "gdn_prefill_chunk_ms", "gdn_prefill_chunk_mfu", "gdn_experts_hit_avg",
         "gdn_picks_held_share", "gdn_rows_live_avg")
CELL = "qwen3next.longanswer_closed"


def reader(name):
    return run.load_module(BENCH / "layer_metrics" / f"{name}.py")


def test_the_bytes_of_the_cut_at_the_published_widths():
    layers = CONF["num_hidden_layers"]
    assert rl.kinds(CONF) == (layers - layers // 4, layers // 4) and rl.chips(CONF) == 4
    # a linear layer 33.72 M parameters outside its FFN, a full layer 27.27 M,
    # router + shared expert + gate + norm 4.20 M, one expert 3.146 M
    assert rl._linear_params(CONF) == pytest.approx(33.72e6, rel=1e-3)
    assert rl._attn_params(CONF) == pytest.approx(27.27e6, rel=1e-3)
    assert rl._moe_fixed_params(CONF) == 2048 * 512 + 3 * 2048 * 512 + 2 * 2048
    assert rl.expert_bytes(CONF) == 6 * 2048 * 512 == 6291456
    if layers == 12:   # the issue's arithmetic: 5.42 B parameters, 10.85 GB
        assert 2 * rl.param_count(CONF) == pytest.approx(10.85e9, rel=1e-3)
    assert rl.state_layer_bytes(CONF) == 32 * 128 * 128 * 4         # 2 MiB a slot a layer
    assert rl.tail_layer_bytes(CONF) == 4 * 8192 * 2
    assert rl.kv_token_bytes(CONF) == 2 * 2 * 256 * 2               # K and V, 2 kv heads of 256
    lin, full = rl.kinds(CONF)
    need = rl.decode_step_bytes(CONF, 30, 30 * 3500, 3.0)
    assert need - rl.non_expert_weight_bytes(CONF) == pytest.approx(
        layers * 3 * 6291456 + 2 * 30 * lin * (2097152 + 65536) + full * 30 * 3500 * 2048
        + 2 * 30 * 2048)
    assert rl.state_step_call_bytes(CONF, 30) == 2 * 30 * 2097152
    # a token's least operations: more held picks, more operations; a prompt
    # row adds one head row
    a, b = (rl.chunk_min_flops(CONF, 1, 256, 256 * 257 // 2, s) for s in (0.25, 0.3))
    assert b - a == pytest.approx(256 * layers * 2 * 0.05 * 10 * 3 * 2048 * 512)
    assert rl.chunk_min_flops(CONF, 2, 256, 0, 0.3) - rl.chunk_min_flops(CONF, 1, 256, 0, 0.3) == (
        2 * 2048 * CONF["vocab_size"])


def burst(t1, rows, steps, hit, held_of_ten, layers=12):
    """One decode burst's ``batcher.readback`` record."""
    samples = layers * steps
    return ("batcher.readback", t1 - 0.01, t1, {
        "program": "decode", "state_rows": rows * steps, "state_steps": steps,
        "state_slots_moved": rows * steps, "experts_hit": hit * samples,
        "expert_rows_max": rows * samples, "expert_rows": rows * samples,
        "expert_steps": samples, "moe_picks": 10 * rows * samples,
        "moe_picks_held": held_of_ten * rows * samples, "expert_path": "hit_list"})


def test_the_gdn_counter_readers_sum_the_windows_own_bursts():
    src = {"config": CONF, "window": (10.0, 20.0),
           "spans": [burst(9.5, 32, 8, 9, 5), burst(12.0, 24, 8, 3, 3), burst(15.0, 30, 8, 3, 3),
                     burst(21.0, 1, 8, 1, 1),
                     ("batcher.readback", 13.0, 13.1, {"program": "admit"})]}
    assert reader("gdn_rows_live_avg").read(src) == pytest.approx(27.0)
    assert reader("gdn_experts_hit_avg").read(src) == pytest.approx(3.0)
    assert reader("gdn_picks_held_share").read(src) == pytest.approx(30.0)
    for name in ("gdn_rows_live_avg", "gdn_experts_hit_avg", "gdn_picks_held_share"):
        assert reader(name).read(dict(src, spans=[])) is None
        assert reader(name).read(dict(src, config=OTHER)) is None    # another family's cell
    # a parent's bursts carry no picks: the share is left out, the others read
    bare = [(n, a, b, {k: v for k, v in at.items() if not k.startswith("moe_")})
            for n, a, b, at in src["spans"]]
    assert reader("gdn_picks_held_share").read(dict(src, spans=bare)) is None
    assert reader("gdn_rows_live_avg").read(dict(src, spans=bare)) == pytest.approx(27.0)


def test_the_gdn_trace_readers_divide_whole_launches_and_the_kernels_own_events():
    state = "f32[32,9,32,128,128]{4,3,2,1,0}"
    planes = {"/device:TPU:0": {
        "XLA Modules": [("jit_decode_pos_moe(1)", 0, 100 * MS),        # first: left out
                        ("jit_decode_pos_moe(1)", 100 * MS, 40 * MS),
                        ("jit_prefill_chunk_group(2)", 140 * MS, 30 * MS),
                        ("jit_prefill1(3)", 170 * MS, 10 * MS),
                        ("jit_decode_pos_moe(1)", 300 * MS, 40 * MS),
                        ("jit_decode_pos_moe(1)", 460 * MS, 100 * MS)],  # last: left out
        "XLA Ops": [(f"%gated_delta_step.7 = {state} custom-call(...)", 110 * MS, 200_000),
                    (f"%gated_delta_step.8 = {state} custom-call(...)", 120 * MS, 240_000),
                    ("%paged_decode_attention.3 = bf16[32,1,16,256]{3,2,1,0} custom-call(...)",
                     130 * MS, 90_000)]}}
    from benchmark.lib import reduce_trace as rt

    chunk = {"program": "chunk", "rows": 2, "width": 2, "tokens": 512, "live_keys": 1024,
             "pairs": 2 * (256 * 256 + 256 * 257 // 2)}
    src = {"config": CONF, "device": DEVICE, "planes": planes, "trace": rt.reduce(planes),
           "engine": {"decode_burst": 8}, "window": (10.0, 20.0), "span": (14.0, 16.0),
           "spans": [burst(15.0, 27, 8, 3, 3), burst(19.0, 9, 8, 3, 3),
                     ("batcher.admit", 15.2, 15.3, chunk)],
           "samples": [{"pool": {"blocks_live": 6000, "block_tokens": 16}}]}
    # a step is 40 ms / 8 = 5 ms of the two whole launches; the span's own
    # burst holds 27 rows (the window's other burst, 9 rows, is not priced)
    need = rl.decode_step_bytes(CONF, 27.0, 96000.0, 3.0)
    assert reader("gdn_decode_step_roofline").read(src) == pytest.approx(
        100.0 * need / 819e9 / 0.005)
    # a call is 220 us on average; 27 rows' state of a layer in and out is 113 MB
    assert reader("gdn_state_step_roofline").read(src) == pytest.approx(
        100.0 * 2 * 27 * 2097152 / 819e9 / 220e-6)
    # (30 + 10) ms over the two chunk launches
    assert reader("gdn_prefill_chunk_ms").read(src) == pytest.approx(20.0)
    # the span's one record is a mean launch; two launches of it in 40 ms
    flops = rl.chunk_min_flops(CONF, 2, 512, chunk["pairs"], 0.3)
    assert reader("gdn_prefill_chunk_mfu").read(src) == pytest.approx(
        100.0 * flops * 2 / 0.040 / 197e12)
    assert 0 < reader("gdn_prefill_chunk_mfu").read(src) < 100
    for name in NAMES[:5]:
        assert reader(name).read(dict(src, config=OTHER)) is None   # another family's cell
        assert reader(name).read(dict(src, planes={}, trace={"device_planes": 0})) is None
    # no record of a chunk launch in the span (a parent's batcher writes none
    # for this family): the share of the peak is left out, the milliseconds read
    none = dict(src, spans=src["spans"][:2])
    assert reader("gdn_prefill_chunk_mfu").read(none) is None
    assert reader("gdn_prefill_chunk_ms").read(none) == pytest.approx(20.0)


def test_the_scope_share_reads_the_decode_programs_time_under_seq_linear(monkeypatch):
    from benchmark.lib import scopes

    table = {"launches": {"decode_pos_moe": {"kind": "decode", "n": 2, "ns": 80e6},
                          "prefill1": {"kind": "prefill", "n": 1, "ns": 10e6}},
             "ops": {("decode_pos_moe", "seq/linear"): [20e6, 100],
                     ("decode_pos_moe", "seq/attn"): [8e6, 30],
                     ("decode_pos_moe", "ffn/experts"): [40e6, 90],
                     ("decode_pos_moe", None): [12e6, 50],
                     ("prefill1", "seq/linear"): [5e6, 10]}}
    monkeypatch.setattr(scopes, "table", lambda src: table)
    assert reader("gdn_seq_linear_share").read({"config": CONF}) == pytest.approx(25.0)
    monkeypatch.setattr(scopes, "table", lambda src: None)
    assert reader("gdn_seq_linear_share").read({"config": CONF}) is None
    # a parent's program opens no such scope: nothing under it, nothing read
    bare = dict(table, ops={k: v for k, v in table["ops"].items() if k[1] != "seq/linear"})
    monkeypatch.setattr(scopes, "table", lambda src: bare)
    assert reader("gdn_seq_linear_share").read({"config": CONF}) is None


def test_the_gdn_entries_are_in_the_manifest_for_the_new_cell_alone():
    man = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    by_name = {m["name"]: m for m in man["per_layer"]}
    for name in NAMES:
        assert by_name[name] == dict(reader(name).METRIC, workloads=[CELL]), name
    cell = next(w for w in man["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": "qwen3-next-80b-a3b-instruct",
                    "traffic": "longanswer_closed", "chips": 1, "why": cell["why"]}
    conf = next(c for c in man["configs"] if c["name"] == cell["config"])
    assert conf["reduced"] == CONF["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    mix = json.loads((BENCH / "traffic" / "longanswer_closed.json").read_text())
    assert (mix["callers"], mix["deck"], mix["greedy_every"], mix["temperature"]) == (32, 64, 4, 0.8)
    assert mix["prompt_tokens"] == {"dist": "loguniform", "min": 256, "max": 2048}
    assert mix["output_tokens"] == {"dist": "loguniform", "min": 1024, "max": 4096}
    assert int(CONF["serving"]["env"]["MAX_BATCH_SLOTS"]) == mix["callers"]


def test_the_configuration_keeps_every_published_width():
    """Every number of the catalog's row is in the file under its own key;
    only the three keys of ``reduced`` differ, and ``published`` holds what
    they were."""
    row = {"decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
           "hidden_size": 2048, "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
           "linear_key_head_dim": 128, "linear_num_key_heads": 16, "linear_num_value_heads": 32,
           "linear_value_head_dim": 128, "max_position_embeddings": 262144,
           "moe_intermediate_size": 512, "num_attention_heads": 16, "num_experts": 512,
           "num_experts_per_tok": 10, "num_hidden_layers": 48, "num_key_value_heads": 2,
           "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06, "rope_theta": 10000000,
           "shared_expert_intermediate_size": 512, "vocab_size": 151936}
    differ = {k for k, v in row.items() if CONF[k] != v}
    assert differ == set(CONF["reduced"])
    assert CONF["published"] == {k: row[k] for k in CONF["reduced"]}
    assert CONF["num_experts"] * CONF["expert_parallel"]["chips"] == row["num_experts"]
    assert CONF["vocab_size"] * 4 == row["vocab_size"] and CONF["num_hidden_layers"] % 4 == 0
    assert CONF["norm_topk_prob"] is True and CONF["tie_word_embeddings"] is False
