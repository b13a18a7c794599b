"""The lightning / block-sparse family's bytes and operations from shapes and
its nine readers on hand-made sources with known answers
(``lib/roofline_sala.py``, ``layer_metrics/sala_*.py``): the arithmetic at the
published widths and the configuration's cut, and a source that lacks what a
reader reads (a parent commit, another family's configuration, an untraced
run) gives ``None`` and never raises."""

import json

import pytest

from benchmark import run
from benchmark.lib import roofline_sala as rl
from benchmark.tests.test_reduce_trace import FIX, MS

BENCH = FIX.parent
CONF = json.loads((BENCH / "configs" / "minicpm-sala.json").read_text())
OTHER = json.loads((BENCH / "configs" / "qwen3-next-80b-a3b-instruct.json").read_text())
DEVICE = {"kind": "TPU v5 lite", "count": 1}
NAMES = ("sala_picked_walk_roofline", "sala_state_step_roofline", "sala_decode_step_roofline",
         "sala_select_ms_per_step", "sala_seq_share", "sala_prefill_chunk_ms",
         "sala_prefill_chunk_mfu", "sala_picked_share", "sala_rows_live_avg")
CELL = "minicpmsala.longdoc_closed"


def reader(name):
    return run.load_module(BENCH / "layer_metrics" / f"{name}.py")


def test_the_bytes_of_the_sala_cut_at_the_published_widths():
    assert rl.is_family(CONF) and not rl.is_family(OTHER)
    assert rl.kinds(CONF) == (6, 2)
    # a lightning layer 285.21 M parameters, a sparse layer 253.76 M, the
    # embedding and the head 601.69 M: 2.82 B, 5.64 GB of bf16 (ISSUE 51's arithmetic)
    assert rl._lightning_params(CONF) == 5 * 4096 ** 2 + 3 * 4096 * 16384
    assert rl._sparse_params(CONF) == 3 * 4096 ** 2 + 2 * 4096 * 256 + 3 * 4096 * 16384
    assert rl.param_count(CONF) == 2_820_569_280       # the served tree's own count
    assert rl.weight_bytes(CONF) == 2 * (6 * rl._lightning_params(CONF)
                                         + 2 * rl._sparse_params(CONF) + 4096 * 73448)
    assert rl.state_layer_bytes(CONF) == 32 * 128 * 128 * 4       # 2 MiB a slot a layer
    assert rl.kv_token_bytes(CONF) == 1024 and rl.pooled_token_bytes(CONF) == 32
    assert rl.state_step_call_bytes(CONF, 15) == 2 * 15 * 2097152
    assert rl.picked_walk_call_bytes(CONF, 15 * 4096) == 15 * 4096 * 1024
    need = rl.decode_step_bytes(CONF, 15, 2 * 15 * 4096, 2 * 15 * 18000)
    assert need - rl.weight_bytes(CONF) == pytest.approx(
        2 * 15 * 6 * 2097152 + 2 * 15 * 4096 * 1024 + 2 * 15 * 18000 * 32 + 2 * 15 * 4096)
    # a chunk past the dense length attends over at most 64 blocks of 64 a
    # query, however many pairs its causal triangle holds; a prompt row adds a head row
    far = rl.chunk_min_flops(CONF, 1, 256, 256 * 20000, 20000)
    near = rl.chunk_min_flops(CONF, 1, 256, 256 * 10000, 20000)
    assert far == near
    under = rl.chunk_min_flops(CONF, 1, 256, 256 * 2000, 20000)
    assert near - under == 2 * 32 * 128 * 4 * 256 * (4096 - 2000)
    assert rl.chunk_min_flops(CONF, 2, 256, 0, 0) - rl.chunk_min_flops(CONF, 1, 256, 0, 0) == (
        2 * 4096 * 73448)


def burst(t1, rows, steps, ctx, layers=2):
    """One decode burst's ``batcher.readback`` record: ``rows`` rows at
    contexts near ``ctx``, past the dense length."""
    return ("batcher.readback", t1 - 0.01, t1, {
        "program": "decode", "state_rows": rows * steps, "state_steps": steps,
        "state_slots_moved": rows * steps, "sparse_tokens_live": layers * rows * steps * ctx,
        "sparse_tokens_picked": layers * rows * steps * 4064, "sparse_rows_dense": 0})


def test_the_sala_counter_readers_sum_the_windows_own_bursts():
    src = {"config": CONF, "window": (10.0, 20.0),
           "spans": [burst(9.5, 16, 8, 9000), burst(12.0, 14, 8, 16256), burst(15.0, 16, 8, 16256),
                     burst(21.0, 1, 8, 9000),
                     ("batcher.readback", 13.0, 13.1, {"program": "admit"})]}
    assert reader("sala_rows_live_avg").read(src) == pytest.approx(15.0)
    assert reader("sala_picked_share").read(src) == pytest.approx(25.0)
    for name in ("sala_rows_live_avg", "sala_picked_share"):
        assert reader(name).read(dict(src, spans=[])) is None
        assert reader(name).read(dict(src, config=OTHER)) is None    # another family's cell
    # a parent's bursts carry no sparse counters: nothing is read
    bare = [(n, a, b, {k: v for k, v in at.items() if not k.startswith("sparse_")})
            for n, a, b, at in src["spans"]]
    assert reader("sala_picked_share").read(dict(src, spans=bare)) is None
    assert reader("sala_rows_live_avg").read(dict(src, spans=bare)) is None


def test_the_sala_trace_readers_divide_whole_launches_and_the_kernels_own_events():
    state = "f32[16,6,32,128,128]{4,3,2,1,0}"
    planes = {"/device:TPU:0": {
        "XLA Modules": [("jit_decode_pos_pallas(1)", 0, 100 * MS),        # first: left out
                        ("jit_decode_pos_pallas(1)", 100 * MS, 64 * MS),
                        ("jit_prefill_chunk_group(2)", 170 * MS, 30 * MS),
                        ("jit_prefill1(3)", 200 * MS, 14 * MS),
                        ("jit_decode_pos_pallas(1)", 300 * MS, 64 * MS),
                        ("jit_decode_pos_pallas(1)", 460 * MS, 100 * MS)],  # last: left out
        "XLA Ops": [(f"%lightning_step.7 = ({state}, f32[16,4096]) custom-call(...)",
                     110 * MS, 100_000),
                    (f"%lightning_step.8 = ({state}, f32[16,4096]) custom-call(...)",
                     120 * MS, 140_000),
                    ("%paged_decode_attention_picked.3 = bf16[32,16,128]{2,1,0} custom-call(...)",
                     130 * MS, 150_000),
                    ("%paged_decode_attention.9 = bf16[32,16,128]{2,1,0} custom-call(...)",
                     135 * MS, 999_000)]}}
    from benchmark.lib import reduce_trace as rt

    chunk = {"program": "chunk", "rows": 2, "width": 2, "tokens": 512, "live_keys": 2 * 12256,
             "pairs": 2 * (256 * 12000 + 256 * 257 // 2)}
    src = {"config": CONF, "device": DEVICE, "planes": planes, "trace": rt.reduce(planes),
           "engine": {"decode_burst": 8}, "window": (10.0, 20.0), "span": (14.0, 16.0),
           "spans": [burst(15.0, 15, 8, 18000), burst(19.0, 9, 8, 18000),
                     ("batcher.admit", 15.2, 15.3, chunk)], "samples": []}
    # a step is 64 ms / 8 = 8 ms of the two whole launches; the span's own
    # burst holds 15 rows (the window's other burst, 9 rows, is not priced)
    need = rl.decode_step_bytes(CONF, 15.0, 2 * 15 * 4064.0, 2 * 15 * 18000.0)
    assert reader("sala_decode_step_roofline").read(src) == pytest.approx(
        100.0 * need / 819e9 / 0.008)
    assert 0 < reader("sala_decode_step_roofline").read(src) < 100
    # a state call is 120 us on average; 15 rows' state of a layer in and out is 63 MB
    assert reader("sala_state_step_roofline").read(src) == pytest.approx(
        100.0 * 2 * 15 * 2097152 / 819e9 / 120e-6)
    # the picked walk alone carries its name: the whole-table walk's event is not it
    assert reader("sala_picked_walk_roofline").read(src) == pytest.approx(
        100.0 * 15 * 4064 * 1024 / 819e9 / 150e-6)
    # (30 + 14) ms over the two chunk launches
    assert reader("sala_prefill_chunk_ms").read(src) == pytest.approx(22.0)
    flops = rl.chunk_min_flops(CONF, 2, 512, chunk["pairs"], chunk["live_keys"])
    assert reader("sala_prefill_chunk_mfu").read(src) == pytest.approx(
        100.0 * flops * 2 / 0.044 / 197e12)
    assert 0 < reader("sala_prefill_chunk_mfu").read(src) < 100
    for name in NAMES[:3] + NAMES[5:7]:
        assert reader(name).read(dict(src, config=OTHER)) is None   # another family's cell
        assert reader(name).read(dict(src, planes={}, trace={"device_planes": 0})) is None
    # a parent's bursts and admits carry no such counters: the shares are left out
    none = dict(src, spans=[])
    for name in ("sala_decode_step_roofline", "sala_state_step_roofline",
                 "sala_picked_walk_roofline", "sala_prefill_chunk_mfu"):
        assert reader(name).read(none) is None
    assert reader("sala_prefill_chunk_ms").read(none) == pytest.approx(22.0)


def test_the_scope_readers_read_the_decode_programs_time_under_the_mixers(monkeypatch):
    from benchmark.lib import scopes

    table = {"launches": {"decode_pos_pallas": {"kind": "decode", "n": 2, "ns": 128e6},
                          "prefill1": {"kind": "prefill", "n": 1, "ns": 10e6}},
             "ops": {("decode_pos_pallas", "seq/linear"): [24e6, 100],
                     ("decode_pos_pallas", "seq/sparse"): [6e6, 30],
                     ("decode_pos_pallas", "seq/sparse/select"): [1.6e6, 40],
                     ("decode_pos_pallas", "seq/sparse/pool"): [0.4e6, 8],
                     ("decode_pos_pallas", "ffn/mlp"): [80e6, 90],
                     ("decode_pos_pallas", None): [16e6, 50],
                     ("prefill1", "seq/sparse/select"): [5e6, 10]}}
    monkeypatch.setattr(scopes, "table", lambda src: table)
    monkeypatch.setattr(scopes, "_steps_a_launch", lambda src: 8.0)
    assert reader("sala_seq_share").read({"config": CONF}) == pytest.approx(25.0)
    # 1.6 ms under the selection over 2 launches x 8 steps
    assert reader("sala_select_ms_per_step").read({"config": CONF}) == pytest.approx(0.1)
    assert reader("sala_seq_share").read({"config": OTHER}) is None
    monkeypatch.setattr(scopes, "table", lambda src: None)
    assert reader("sala_seq_share").read({"config": CONF}) is None
    assert reader("sala_select_ms_per_step").read({"config": CONF}) is None
    # a parent's program opens no such scope: nothing under it, nothing read
    bare = dict(table, ops={k: v for k, v in table["ops"].items()
                            if not (k[1] or "").startswith("seq/")})
    monkeypatch.setattr(scopes, "table", lambda src: bare)
    assert reader("sala_seq_share").read({"config": CONF}) is None
    assert reader("sala_select_ms_per_step").read({"config": CONF}) is None


def test_the_sala_entries_are_in_the_manifest_for_the_new_cell_alone():
    man = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    by_name = {m["name"]: m for m in man["per_layer"]}
    for name in NAMES:
        assert by_name[name] == dict(reader(name).METRIC, workloads=[CELL]), name
    cell = next(w for w in man["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": "minicpm-sala", "traffic": "longdoc_closed",
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "no layer is divided" in cell["why"]
    conf = next(c for c in man["configs"] if c["name"] == cell["config"])
    assert conf["reduced"] == CONF["reduced"] == ["num_hidden_layers", "mixer_types"]
    mix = json.loads((BENCH / "traffic" / "longdoc_closed.json").read_text())
    assert (mix["callers"], mix["deck"], mix["greedy_every"], mix["temperature"]) == (16, 32, 4, 0.8)
    assert mix["prompt_tokens"] == {"dist": "loguniform", "min": 9216, "max": 24576}
    assert mix["output_tokens"] == {"dist": "loguniform", "min": 1024, "max": 4096}
    env = CONF["serving"]["env"]
    assert int(env["MAX_BATCH_SLOTS"]) == mix["callers"]
    assert int(env["MAX_SEQ_LEN"]) == mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    assert mix["prompt_tokens"]["min"] > CONF["sparse_config"]["dense_len"]
    assert int(env["KV_BLOCK_TOKENS"]) == CONF["sparse_config"]["block_size"]


def test_the_sala_configuration_keeps_every_published_key():
    """Every key of the catalog's row is in the file under its own name; only
    the two keys of ``reduced`` differ, and ``published`` holds what they
    were: the cut is entries 9-16 of the published list."""
    row = eval(ROW)  # noqa: S307 — this file's own literal
    differ = {k for k, v in row.items() if CONF[k] != v}
    assert differ == set(CONF["reduced"])
    assert CONF["published"] == {k: row[k] for k in CONF["reduced"]}
    first = CONF["stage_first_layer"]
    assert CONF["mixer_types"] == row["mixer_types"][first: first + CONF["num_hidden_layers"]]
    assert CONF["mixer_types"] == ["minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"]
    assert row["mixer_types"].count("lightning-attn") == 24
    assert len(CONF["assumed"]) >= 8 and all(isinstance(a, str) for a in CONF["assumed"])


ROW = """{
 "attention_bias": False, "attn_use_rope": False, "head_dim": 128, "hidden_act": "silu",
 "hidden_size": 4096, "intermediate_size": 16384, "lightning_head_dim": 128, "lightning_nh": 32,
 "lightning_nkv": 32, "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
 "max_position_embeddings": 524288, "model_type": "minicpm_sala",
 "mixer_types": ["minicpm4"] + ["lightning-attn"] * 8 + ["minicpm4"] + ["lightning-attn"] * 6
                + ["minicpm4"] * 2 + ["lightning-attn"] * 4 + ["minicpm4"]
                + ["lightning-attn"] * 6 + ["minicpm4"] * 3,
 "num_attention_heads": 32, "num_hidden_layers": 32, "num_key_value_heads": 2, "qk_norm": True,
 "rand_init": False, "rms_norm_eps": 1e-06, "vocab_size": 73448, "rope_theta": 10000,
 "scale_emb": 12, "scale_depth": 1.4, "mup_denominator": 32, "dim_model_base": 256,
 "tie_word_embeddings": False, "use_output_gate": True, "use_output_norm": True,
 "attn_use_output_gate": True}"""
