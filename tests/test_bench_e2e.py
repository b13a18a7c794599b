"""The driver-visible bench's end-to-end NATS mode must keep working: it is
the artifact that records TTFT/throughput each round. Smoke it at tiny scale
on the CPU backend."""

import sys
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).parent.parent))


def test_e2e_nats_bench_smoke():
    import bench
    from nats_llm_studio_tpu.models.config import ModelConfig
    from nats_llm_studio_tpu.models.llama import ensure_lm_head, init_params

    cfg = ModelConfig.tiny(vocab_size=300, n_layers=2, max_seq_len=256)
    params = ensure_lm_head(init_params(cfg, jax.random.PRNGKey(0)))
    out = bench.e2e_nats_bench(cfg, params, "bench/tiny", clients_a=2, clients_b=2)
    assert set(out) >= {"ttft_p50_ms", "ttft_p95_ms", "e2e_tok_s",
                        "ttft_clients", "e2e_tok_s_clients", "transport_rt_ms"}
    assert out["ttft_clients"] == 2 and out["e2e_tok_s_clients"] == 2
    assert out["ttft_p50_ms"] > 0 and out["e2e_tok_s"] > 0
    # per-phase occupancy + queue-delay + parse-failure fields exist
    assert out["throughput_wave"]["parse_failures"] == 0
    assert "tokens_per_step_avg" in out["throughput_wave"]["batcher_phase"]
    assert "admit_queue_delay_p95_ms" in out["throughput_wave"]["batcher_phase"]
    # round-5 phases: ring-compaction recovery + bounded-overload shedding
    ring = out["ring_compaction"]
    assert ring["parse_failures"] == 0
    assert {"ring_compactions", "survivor_gap_post_roll_p50_ms"} <= set(ring)
    ov = out["overload"]
    assert ov["completed"] >= 1
    assert "admit_queue_delay_p95_ms" in ov["batcher_phase"]
    assert "batcher_shed_total" in ov and "sheds_observed_by_clients" in ov
    # bounds were restored after the overload phase
    assert "shed" in out["batcher"] and "cancelled" in out["batcher"]


def test_moe_bench_smoke():
    """The MoE routed-vs-dense ablation path must run (tiny geometry on
    CPU); speedup ratios are reported, both dispatch forms measured."""
    import bench
    from nats_llm_studio_tpu.models.config import ModelConfig

    cfg = ModelConfig.tiny(
        n_experts=4, n_experts_used=2, d_ff=64, n_layers=2,
        n_heads=4, n_kv_heads=2, head_dim=16, dtype="bfloat16",
    )
    out = bench.moe_bench(cfg=cfg, batch=2, prompt_len=8, seq_len=64, steps=4)
    assert out["routed"]["tok_s"] > 0 and out["dense"]["tok_s"] > 0
    assert out["routed_decode_speedup"] > 0
    assert out["routed_prefill_speedup"] > 0
    assert out["geometry"]["n_experts"] == 4
    assert out["prefill_deep"]["routed"] > 0 and out["prefill_deep"]["dense"] > 0
    assert out["prefill_deep"]["routed_speedup"] > 0
    # round-5: small-batch ablation + measured capacity-overflow drop rates
    small = out["small_batch"]
    assert small["b1"]["routed_tok_s"] > 0 and small["b4"]["dense_tok_s"] > 0
    assert 0.0 <= small["drop_fraction"]["decode_b1"] <= 1.0
    assert "prefill_4x128" in small["drop_fraction"]


def test_obs_overhead_bench_smoke():
    """The flight-recorder overhead phase must run at tiny scale: both arms
    measured, the recorder-on arm actually sampled frames, and both arms
    served every token. The overhead itself is reported, not bounded: a CPU
    timing under xdist says nothing, the chip's reading is in PERF.md."""
    import bench
    from nats_llm_studio_tpu.models.config import ModelConfig
    from nats_llm_studio_tpu.models.llama import ensure_lm_head, init_params

    cfg = ModelConfig.tiny(vocab_size=300, n_layers=2, max_seq_len=256)
    params = ensure_lm_head(init_params(cfg, jax.random.PRNGKey(0)))
    out = bench.obs_overhead_bench(
        cfg, params, seq=128, slots=2, n_reqs=2, max_new=12, rounds=2
    )
    assert out["frames_sampled"] > 0
    assert len(out["off_tok_s"]) == 2 and len(out["on_tok_s"]) == 2
    assert out["off_median_tok_s"] > 0 and out["on_median_tok_s"] > 0
    assert out["off_tokens_served"] == out["on_tokens_served"] == 3 * 2 * 12
    assert isinstance(out["overhead_pct"], float) and out["noise_floor_pct"] >= 0.0


def test_e2e_long_context_bench_smoke(monkeypatch):
    """The long-context serving wave (VERDICT r3 missing #1) at tiny scale:
    real prompt_tokens come back from usage, interference gaps and
    per-phase batcher stats are recorded."""
    import bench
    from nats_llm_studio_tpu.models.config import ModelConfig
    from nats_llm_studio_tpu.models.llama import ensure_lm_head, init_params

    monkeypatch.setenv("BENCH_LONG_SEQ", "256")
    monkeypatch.setenv("BENCH_LONG_SLOTS", "4")
    monkeypatch.setenv("BENCH_LONG_CHUNK", "32")
    cfg = ModelConfig.tiny(vocab_size=300, n_layers=2, max_seq_len=256)
    params = ensure_lm_head(init_params(cfg, jax.random.PRNGKey(0)))
    monkeypatch.setenv("BENCH_XL_SEQ", "256")
    out = bench.e2e_long_context_bench(
        cfg, params, "bench/tiny", n_long=2, long_tokens=150, xl_tokens=200
    )
    lw = out["long_wave"]
    # prompt token counts are MEASURED (usage block), >= the requested size
    assert lw["prompt_tokens_each"] >= 150
    assert out["xl_single"]["prompt_tokens"] >= 200
    assert lw["parse_failures"] == 0
    assert lw["ttft_p50_ms"] > 0 and lw["prefill_tok_s"] > 0
    assert lw["interference_gap_p95_ms"] >= lw["interference_gap_p50_ms"] >= 0
    assert lw["batcher_phase"]["tokens"] > 0
