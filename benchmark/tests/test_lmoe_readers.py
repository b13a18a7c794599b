"""The latent-expert state-space family's bytes and operations from shapes and
its nine readers on hand-made sources with known answers
(``lib/roofline_ssm_latent_moe.py``, ``layer_metrics/lmoe_*.py``): the
arithmetic at the published widths and the configuration's cut, and a source
that lacks what a reader reads (a parent commit, another family's
configuration, an untraced run) gives ``None`` and never raises."""

import json

import pytest

from benchmark import run
from benchmark.lib import roofline_ssm_latent_moe as rl
from benchmark.tests.test_reduce_trace import FIX, MS

BENCH = FIX.parent
CONF = json.loads((BENCH / "configs" / "nemotron-3-super-120b-a12b.json").read_text())
OTHER = json.loads((BENCH / "configs" / "granite-4.0-h-micro.json").read_text())
DEVICE = {"kind": "TPU v5 lite", "count": 1}
NAMES = ("lmoe_state_step_roofline", "lmoe_decode_step_roofline", "lmoe_experts_roofline",
         "lmoe_prefill_chunk_ms", "lmoe_prefill_chunk_mfu", "lmoe_experts_hit_avg",
         "lmoe_picks_held_share", "lmoe_rows_live_avg", "lmoe_state_pool_used_share")
CELL = "nemotron3super.agent64_closed"


def reader(name):
    return run.load_module(BENCH / "layer_metrics" / f"{name}.py")


def test_the_bytes_of_the_latent_expert_cut_at_the_published_widths():
    assert rl.kinds(CONF) == (5, 5, 1) and rl.chips(CONF) == 4 and rl.is_family(CONF)
    assert not rl.is_family(OTHER)
    # the issue's count from the keys: M 109.64 M, * 35.66 M, E 54.53 M outside
    # its experts, an expert 5.505 M; the cut 4.648 B parameters, 9.30 GB
    assert rl._mamba_params(CONF) == pytest.approx(109.64e6, rel=1e-4)
    assert rl._attn_params(CONF) == pytest.approx(35.66e6, rel=1e-3)
    assert rl._moe_fixed_params(CONF) == pytest.approx(54.53e6, rel=1e-4)
    assert rl._expert_params(CONF) == 2 * 1024 * 2688 == 5505024
    assert rl.expert_bytes(CONF) == 11010048
    assert 2 * rl.param_count(CONF) == pytest.approx(9.30e9, rel=1e-3)
    whole = dict(CONF, **CONF["published"], expert_parallel={"chips": 1, "rank": 0})
    assert rl.param_count(whole) == pytest.approx(120.67e9, rel=1e-3)   # the model's name
    assert rl.state_layer_bytes(CONF) == 128 * 64 * 128 * 4            # 4.19 MB a slot a layer
    assert rl.tail_layer_bytes(CONF) == 4 * 10240 * 2 and rl.conv_dim(CONF) == 8192 + 2048
    assert rl.kv_token_bytes(CONF) == 2 * 2 * 128 * 2                   # 1 KB a token
    need = rl.decode_step_bytes(CONF, 60, 60 * 1500, 117.0)
    assert need - rl.non_expert_weight_bytes(CONF) == pytest.approx(
        5 * 117 * 11010048 + 2 * 60 * 5 * (4194304 + 81920) + 60 * 1500 * 1024 + 2 * 60 * 4096)
    assert rl.non_expert_weight_bytes(CONF) == pytest.approx(1.98e9, rel=5e-3)
    assert rl.state_step_call_bytes(CONF, 60) == 2 * 60 * 4194304
    # a token's least operations: more held picks, more operations; a prompt
    # row adds one head row
    a, b = (rl.chunk_min_flops(CONF, 1, 256, 256 * 257 // 2, s) for s in (0.25, 0.3))
    assert b - a == pytest.approx(256 * 5 * 2 * 0.05 * 22 * 5505024)
    assert rl.chunk_min_flops(CONF, 2, 256, 0, 0.25) - rl.chunk_min_flops(
        CONF, 1, 256, 0, 0.25) == 2 * 4096 * CONF["vocab_size"]


def burst(t1, rows, steps, hit, held_of_22, layers=5):
    """One decode burst's ``batcher.readback`` record."""
    samples = layers * steps
    return ("batcher.readback", t1 - 0.01, t1, {
        "program": "decode", "state_rows": rows * steps, "state_steps": steps,
        "state_slots_moved": rows * steps, "experts_hit": hit * samples,
        "expert_rows_max": 6 * samples, "expert_rows": rows * samples,
        "expert_steps": samples, "moe_picks": 22 * rows * samples,
        "moe_picks_held": held_of_22 * rows * samples, "expert_path": "grouped"})


def test_the_lmoe_counter_readers_sum_the_windows_own_bursts():
    pool = lambda live: {"pool": {"state": {"slots_live": live, "slots_total": 64}}}  # noqa: E731
    src = {"config": CONF, "window": (10.0, 20.0), "samples": [pool(64), pool(56), {}],
           "spans": [burst(9.5, 64, 8, 128, 11), burst(12.0, 60, 8, 118, 5.5),
                     burst(15.0, 56, 8, 114, 5.5), burst(21.0, 1, 8, 1, 1),
                     ("batcher.readback", 13.0, 13.1, {"program": "admit"})]}
    assert reader("lmoe_rows_live_avg").read(src) == pytest.approx(58.0)
    assert reader("lmoe_experts_hit_avg").read(src) == pytest.approx(116.0)
    assert reader("lmoe_picks_held_share").read(src) == pytest.approx(25.0)
    assert reader("lmoe_state_pool_used_share").read(src) == pytest.approx(93.75)
    for name in NAMES[5:]:
        assert reader(name).read(dict(src, spans=[], samples=[])) is None
        assert reader(name).read(dict(src, config=OTHER)) is None    # another family's cell
    # a parent's bursts carry no picks: the share is left out, the others read
    bare = [(n, a, b, {k: v for k, v in at.items() if not k.startswith("moe_")})
            for n, a, b, at in src["spans"]]
    assert reader("lmoe_picks_held_share").read(dict(src, spans=bare)) is None
    assert reader("lmoe_rows_live_avg").read(dict(src, spans=bare)) == pytest.approx(58.0)


def test_the_lmoe_trace_readers_divide_whole_launches_and_the_kernels_own_events():
    state = "f32[64,5,64,128,128]{4,3,2,1,0}"
    planes = {"/device:TPU:0": {
        "XLA Modules": [("jit_decode_pos_moe(1)", 0, 100 * MS),        # first: left out
                        ("jit_decode_pos_moe(1)", 100 * MS, 160 * MS),
                        ("jit_prefill_chunk_group(2)", 260 * MS, 50 * MS),
                        ("jit_prefill1(3)", 310 * MS, 30 * MS),
                        ("jit_decode_pos_moe(1)", 400 * MS, 160 * MS),
                        ("jit_decode_pos_moe(1)", 600 * MS, 100 * MS)],  # last: left out
        "XLA Ops": [(f"%ssm_state_step.7 = {state} custom-call(...)", 110 * MS, 800_000),
                    (f"%ssm_state_step.8 = {state} custom-call(...)", 120 * MS, 1_000_000),
                    ("%moe_grouped_experts.3 = f32[1408,1024]{1,0} custom-call(...)",
                     130 * MS, 1_900_000),
                    ("%moe_grouped_experts.3 = f32[1408,1024]{1,0} custom-call(...)",
                     410 * MS, 2_100_000),
                    # a chunk launch's call of the same kernel: not a decode step's
                    ("%moe_grouped_experts.9 = f32[22528,1024]{1,0} custom-call(...)",
                     270 * MS, 9_000_000),
                    ("%paged_decode_attention.3 = bf16[64,1,32,128]{3,2,1,0} custom-call(...)",
                     140 * MS, 90_000)]}}
    from benchmark.lib import reduce_trace as rt

    chunk = {"program": "chunk", "rows": 2, "width": 2, "tokens": 512, "live_keys": 1024,
             "pairs": 2 * (256 * 256 + 256 * 257 // 2)}
    src = {"config": CONF, "device": DEVICE, "planes": planes, "trace": rt.reduce(planes),
           "engine": {"decode_burst": 8}, "window": (10.0, 20.0), "span": (14.0, 16.0),
           "spans": [burst(15.0, 60, 8, 117, 5.5), burst(19.0, 9, 8, 30, 5.5),
                     ("batcher.admit", 15.2, 15.3, chunk)],
           "samples": [{"pool": {"blocks_live": 6000, "block_tokens": 16}}]}
    # a step is 160 ms / 8 = 20 ms of the two whole launches; the span's own
    # burst holds 60 rows and hits 117 experts a layer
    need = rl.decode_step_bytes(CONF, 60.0, 96000.0, 117.0)
    assert reader("lmoe_decode_step_roofline").read(src) == pytest.approx(
        100.0 * need / 819e9 / 0.020)
    assert 0 < reader("lmoe_decode_step_roofline").read(src) < 100
    # a call of the state kernel is 0.9 ms on average; 60 rows' state in and out is 503 MB
    assert reader("lmoe_state_step_roofline").read(src) == pytest.approx(
        100.0 * 2 * 60 * 4194304 / 819e9 / 900e-6)
    # a decode step's expert call is 2.0 ms on average (the chunk's 9 ms call is
    # not among them); 117 experts of 11.0 MB
    assert reader("lmoe_experts_roofline").read(src) == pytest.approx(
        100.0 * 117 * 11010048 / 819e9 / 2.0e-3)
    # (50 + 30) ms over the two chunk launches
    assert reader("lmoe_prefill_chunk_ms").read(src) == pytest.approx(40.0)
    flops = rl.chunk_min_flops(CONF, 2, 512, chunk["pairs"], 0.25)
    assert reader("lmoe_prefill_chunk_mfu").read(src) == pytest.approx(
        100.0 * flops * 2 / 0.080 / 197e12)
    assert 0 < reader("lmoe_prefill_chunk_mfu").read(src) < 100
    for name in NAMES[:5]:
        assert reader(name).read(dict(src, config=OTHER)) is None   # another family's cell
        assert reader(name).read(dict(src, planes={}, trace={"device_planes": 0})) is None
    # no record of a chunk launch in the span (a parent's batcher writes none
    # for this family): the share of the peak is left out, the milliseconds read
    none = dict(src, spans=src["spans"][:2])
    assert reader("lmoe_prefill_chunk_mfu").read(none) is None
    assert reader("lmoe_prefill_chunk_ms").read(none) == pytest.approx(40.0)


def test_the_lmoe_entries_are_in_the_manifest_for_the_new_cell_alone():
    man = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    by_name = {m["name"]: m for m in man["per_layer"]}
    for name in NAMES:
        assert by_name[name] == dict(reader(name).METRIC, workloads=[CELL]), name
    assert not [m["name"] for m in man["per_layer"]
                if CELL in m.get("workloads", []) and not m["name"].startswith("lmoe_")]
    cell = next(w for w in man["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": "nemotron-3-super-120b-a12b",
                    "traffic": "agent64_closed", "chips": 1, "why": cell["why"]}
    assert man["workloads"][-1] == cell and len(man["workloads"]) == 9
    conf = next(c for c in man["configs"] if c["name"] == cell["config"])
    assert conf["reduced"] == CONF["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers"]
    mix = json.loads((BENCH / "traffic" / "agent64_closed.json").read_text())
    assert (mix["callers"], mix["deck"], mix["greedy_every"], mix["temperature"]) == (64, 64, 4, 0.8)
    assert mix["prompt_tokens"] == {"dist": "loguniform", "min": 256, "max": 2048}
    assert mix["output_tokens"] == {"dist": "loguniform", "min": 256, "max": 1024}
    env = CONF["serving"]["env"]
    assert int(env["MAX_BATCH_SLOTS"]) == mix["callers"]
    assert int(env["MAX_SEQ_LEN"]) >= mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]


def test_the_latent_expert_configuration_keeps_every_published_width():
    """Every number of the catalog's row is in the file under its own key;
    only the five keys of ``reduced`` differ, and ``published`` holds what they
    were."""
    row = {"chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128, "hidden_size": 4096,
           "intermediate_size": 2688, "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
           "mamba_num_heads": 128, "max_position_embeddings": 262144,
           "moe_intermediate_size": 2688, "moe_latent_size": 1024,
           "moe_shared_expert_intermediate_size": 5376, "n_group": 1, "n_groups": 8,
           "n_routed_experts": 512, "n_shared_experts": 1, "norm_eps": 1e-05,
           "num_attention_heads": 32, "num_experts_per_tok": 22, "num_hidden_layers": 88,
           "num_key_value_heads": 2, "num_logits_to_keep": 1, "num_nextn_predict_layers": 1,
           "partial_rotary_factor": 1, "rope_theta": 10000, "routed_scaling_factor": 5,
           "ssm_state_size": 128, "time_step_floor": 0.0001, "time_step_max": 0.1,
           "time_step_min": 0.001, "topk_group": 1, "vocab_size": 131072,
           "hybrid_override_pattern": (
               "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
               "EMEMEMEM*EMEMEMEME")}
    differ = {k for k, v in row.items() if CONF[k] != v}
    assert differ == set(CONF["reduced"])
    assert CONF["published"] == {k: row[k] for k in CONF["reduced"]}
    assert len(row["hybrid_override_pattern"]) == 88
    assert CONF["hybrid_override_pattern"] == row["hybrid_override_pattern"][:11] == "MEMEMEM*EME"
    assert CONF["n_routed_experts"] * CONF["expert_parallel"]["chips"] == row["n_routed_experts"]
    assert CONF["vocab_size"] * 4 == row["vocab_size"]
    assert CONF["norm_topk_prob"] is True and CONF["tie_word_embeddings"] is False
    assert CONF["mlp_hidden_act"] == "relu2" and CONF["mtp_hybrid_override_pattern"] == "*E"
