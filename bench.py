"""Benchmark: the north-star metric on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "detail"}.

Headline (BASELINE.md config 2, the metric string itself names the model):
**Llama-3-8B geometry, int8 weight-only + int8 KV, batched ring decode** —
batch sweep up to 96, best batch reported. Also measured, in `detail`:

* `e2e` — the SAME 8B engine served end-to-end over the NATS wire
  (`lmstudio.chat_model` streaming): TTFT p50/p95 at 8 clients; aggregate
  tok/s at 96 clients for 128- and 256-token streams, synchronized-wave
  AND closed-loop (sustained); per-phase batcher occupancy and admit
  queue-delay percentiles. The honest "via nats req" numbers.
* `e2e_long` — long-context SERVING: a >=4k-token 4-client wave with
  interference streams (chunked group admission) and a ~8k-token single,
  TTFT / prefill tok/s / inter-chunk gap percentiles, prompt token counts
  read back from usage.
* `long_prefill` — single-dispatch 16k-token flash prefill (SURVEY §5
  long-context), tok/s and seconds.
* `prefix_cache` — shared-system-prompt serving with the automatic prefix
  KV cache ON vs OFF (serve/prefix_cache.py): TTFT p50 and total prefill
  seconds for the same sequential turn mix, plus the scraped
  `lmstudio_prefix_cache_hit_tokens_total` Prometheus counter.
* `moe` — scaled Mixtral geometry (8 experts, top-2) on-chip: decode tok/s
  and prefill for BOTH dispatch forms (routed vs dense).
* `granite2b` — config-1 parity (the round-1/2 flagship), decode tok/s.

Weights are random (throughput depends on shapes/dtypes, not values); the 8B
bf16 tree would not fit HBM next to its int8 copy, so init streams one leaf
at a time: create bf16 -> quantize on device -> free (peak = int8 model +
one bf16 leaf). Set JAX_PLATFORMS=cpu BENCH_TINY=1 for a smoke run.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _sync(x) -> None:
    """Force completion of the dispatched work that produces ``x``."""
    jax.block_until_ready(x)

from nats_llm_studio_tpu.engine.sampling import sample
from nats_llm_studio_tpu.models.config import ModelConfig
from nats_llm_studio_tpu.models.llama import forward, init_params, make_cache
from nats_llm_studio_tpu.ops.wquant import (
    quantizable,
    quantize_weight,
    quantize_weight4,
)

NORTH_STAR_TOK_S = 2000.0

# Meta-Llama-3-8B-Instruct geometry (BASELINE.md config 2): 32 layers,
# d=4096, ff=14336, GQA 32q/8kv, head_dim 128, vocab 128256, rope 500k.
LLAMA3_8B = ModelConfig(
    arch="llama",
    vocab_size=128256,
    d_model=4096,
    n_layers=32,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    d_ff=14336,
    rope_theta=500000.0,
    max_seq_len=8192,
    dtype="bfloat16",
)


# granite-3.0-2b-instruct geometry (BASELINE.md config 1). head_dim 64: the
# Pallas paged-decode kernel is not eligible, paged decode takes the XLA path.
GRANITE_2B = ModelConfig(
    arch="granite",
    vocab_size=49152,
    d_model=2048,
    n_layers=40,
    n_heads=32,
    n_kv_heads=8,
    head_dim=64,
    d_ff=8192,
    rope_theta=10000.0,
    max_seq_len=4096,
    embedding_scale=12.0,
    residual_scale=0.22,
    attention_scale=0.015625,
    logit_scale=1.0 / 8.0,
    dtype="bfloat16",
)


def init_params_int8(cfg: ModelConfig, seed: int = 0, mode: str = "int8",
                     group: int = 32):
    """Leaf-streamed random init, quantized on device.

    8B bf16 is ~16 GB — materializing it before quantization would OOM a
    16 GB chip. Each leaf is created and quantized inside one jit program
    (the bf16 original is a program-local transient), then blocked on, so
    peak HBM = quantized model so far + one bf16 leaf. ``mode`` picks the
    device representation: "int8" (per-channel QTensor, the headline) or
    "int4" (grouped QTensor4, the decode_kernel phase's comparison arm).

    Covers the dense and MoE no-bias trees (the schema below mirrors
    models.llama.init_params for those cases); guarded so an attn-bias
    config cannot silently bench an incomplete tree.
    """
    assert not cfg.attn_bias, (
        "init_params_int8 builds the no-bias schema; extend it before "
        f"benching arch={cfg.arch!r} (attn_bias={cfg.attn_bias})"
    )
    dt = cfg.dtype

    @partial(jax.jit, static_argnums=(1,))
    def _randn(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * 0.02).astype(dt)

    @partial(jax.jit, static_argnums=(1,))
    def _randq(k, shape):
        w = (jax.random.normal(k, shape, jnp.float32) * 0.02).astype(dt)
        if mode == "int4":
            return quantize_weight4(w, group=group, device=True)
        return quantize_weight(w, device=True)

    key = jax.random.PRNGKey(seed)
    counter = [0]

    def leaf(name: str, *shape):
        counter[0] += 1
        k = jax.random.fold_in(key, counter[0])
        out = _randq(k, shape) if quantizable(name) else _randn(k, shape)
        jax.block_until_ready(out)
        return out

    L, d, hq, hkv, hd, ff = (
        cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
        cfg.head_dim, cfg.d_ff,
    )
    blocks = {
        "attn_norm": jnp.ones((L, d), dt),
        "ffn_norm": jnp.ones((L, d), dt),
        "wq": leaf("wq", L, d, hq * hd),
        "wk": leaf("wk", L, d, hkv * hd),
        "wv": leaf("wv", L, d, hkv * hd),
        "wo": leaf("wo", L, hq * hd, d),
    }
    if cfg.is_moe:
        e = cfg.n_experts
        blocks |= {
            "router": leaf("router", L, d, e),  # stays bf16 (not in _QUANT_KEYS)
            "w_gate_e": leaf("w_gate_e", L, e, d, ff),
            "w_up_e": leaf("w_up_e", L, e, d, ff),
            "w_down_e": leaf("w_down_e", L, e, ff, d),
        }
    else:
        blocks |= {
            "w_gate": leaf("w_gate", L, d, ff),
            "w_up": leaf("w_up", L, d, ff),
            "w_down": leaf("w_down", L, ff, d),
        }
    return {
        "embed": leaf("embed", cfg.vocab_size, d),
        "out_norm": jnp.ones((d,), dt),
        "lm_head": leaf("lm_head", d, cfg.vocab_size),
        "blocks": blocks,
    }


# ---------------------------------------------------------------------------
# device-side decode throughput (ring-slot scan, the serving hot path shape)
# ---------------------------------------------------------------------------


def decode_bench(cfg, params, batch, prompt_len, seq_len, steps) -> dict:
    fwd = partial(forward, cfg=cfg)

    # donate the cache: timing reruns prefill into the SAME buffers — a
    # second [B, L, Hkv, S, D] cache next to params would OOM at batch 32
    @partial(jax.jit, donate_argnums=(2, 3))
    def prefill(params, tokens, k, v, start):
        logits, k, v = fwd(
            params, tokens=tokens, k_cache=k, v_cache=v, start_pos=start,
            logit_positions=jnp.full((tokens.shape[0],), tokens.shape[1] - 1, jnp.int32),
        )
        return sample(logits[:, -1, :], jax.random.PRNGKey(1), temperature=0.0), k, v

    def bucket_window(max_pos: int) -> int | None:
        w = -(-(max_pos + 1) // 256) * 256
        return w if w < seq_len else None

    @partial(jax.jit, donate_argnums=(2, 3), static_argnums=(4, 6))
    def decode_n(params, tok, k, v, n, pos0, window):
        """n decode steps as one device-side scan: measures chip throughput
        without per-step host dispatch."""

        def body(carry, i):
            tok, k, v = carry
            pos = pos0 + i
            logits, k, v = fwd(params, tokens=tok[:, None], k_cache=k, v_cache=v,
                               start_pos=pos, ring_slot=pos[0] % k.shape[3],
                               attn_window=window)
            nxt = sample(logits[:, -1, :], jax.random.PRNGKey(2), temperature=0.0)
            return (nxt, k, v), nxt

        (tok, k, v), toks = jax.lax.scan(body, (tok, k, v), jnp.arange(n, dtype=jnp.int32))
        return tok, k, v, toks

    k, v = make_cache(cfg, batch, seq_len)
    tokens = jnp.ones((batch, prompt_len), jnp.int32)
    start = jnp.zeros((batch,), jnp.int32)

    tok, k, v = prefill(params, tokens, k, v, start)  # compile
    _sync(tok)
    # best-of-2 timed runs: a single sample can absorb a transient infra
    # stall (the r3 artifact's b64 prefill_s was 8.77 s vs 0.77/1.15 for
    # its neighbors — an outlier, not steady state). Published points must
    # be steady-state (VERDICT r3 weak #2).
    prefill_s = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        tok, k, v = prefill(params, tokens, k, v, start)
        _sync(tok)
        prefill_s = min(prefill_s, time.perf_counter() - t0)

    pos0 = jnp.full((batch,), prompt_len, jnp.int32)
    window = bucket_window(prompt_len + 3 * steps)
    tok, k, v, _ = decode_n(params, tok, k, v, steps, pos0, window)  # compile
    _sync(tok)
    pos0 = pos0 + steps
    t0 = time.perf_counter()
    tok, k, v, toks = decode_n(params, tok, k, v, steps, pos0, window)
    _sync(toks)
    dt = time.perf_counter() - t0
    del k, v, tok, toks
    gc.collect()
    return {
        "tok_s": round(batch * steps / dt, 1),
        "prefill_s": round(prefill_s, 4),
        "step_ms": round(1e3 * dt / steps, 3),
    }


# ---------------------------------------------------------------------------
# MoE decode + dispatch ablation (BASELINE config 4, VERDICT r3 missing #2)
# ---------------------------------------------------------------------------

# Mixtral-8x7B itself (47B params) cannot fit one 16 GB chip even int8, so
# the on-chip MoE number uses a SCALED Mixtral geometry: identical routing
# shape (8 experts, top-2, SwiGLU experts), halved d_model/d_ff, 16 layers
# -> ~5.9 GB int8 expert weights + attention. The measurement of record for
# the routed path (parallel/moe.py) on real silicon.
SCALED_MIXTRAL = ModelConfig(
    arch="llama",
    vocab_size=32000,
    d_model=2048,
    n_layers=16,
    n_heads=16,
    n_kv_heads=8,
    head_dim=128,
    d_ff=7168,
    rope_theta=1e6,
    max_seq_len=4096,
    dtype="bfloat16",
    n_experts=8,
    n_experts_used=2,
)


def moe_bench(cfg=None, batch=32, prompt_len=128, seq_len=512,
              steps=128) -> dict:
    """Decode tok/s and prefill time for the SAME MoE weights under both
    dispatch forms: routed (sparse scatter/gather, parallel/moe.py) and
    dense reference (every expert computes every token, E/k = 4x the
    FLOPs). Decode at serving batch is weight-traffic-bound (both forms
    read all experts), so the FLOP saving shows up at prefill token counts
    — report both rather than cherry-picking."""
    on_tpu = jax.default_backend() == "tpu"
    base = (cfg or SCALED_MIXTRAL).with_(
        use_flash_attention=on_tpu, decode_unroll=True, kv_quant="int8"
    )
    params = init_params_int8(base, seed=2)
    out: dict = {
        "geometry": {
            "d_model": base.d_model, "d_ff": base.d_ff,
            "n_layers": base.n_layers, "n_experts": base.n_experts,
            "n_experts_used": base.n_experts_used, "batch": batch,
        }
    }
    for name, routed in (("routed", True), ("dense", False)):
        out[name] = decode_bench(
            base.with_(use_routed_moe=routed), params, batch, prompt_len,
            seq_len, steps,
        )
    out["routed_decode_speedup"] = round(
        out["routed"]["tok_s"] / out["dense"]["tok_s"], 3
    )
    # prefill covers batch*prompt_len tokens in one dispatch — the
    # FLOP-bound regime where dense dispatch pays E/k x
    out["routed_prefill_speedup"] = round(
        out["dense"]["prefill_s"] / out["routed"]["prefill_s"], 3
    )

    # deep-prefill ablation: at batch*512 tokens the expert FLOPs dominate
    # everything else, so the E/k = 4x dense dispatch waste is maximally
    # visible — the number that justifies the routed path's existence
    long_t = int(os.environ.get("BENCH_MOE_PREFILL", "512"))
    ab = {}
    for nm, routed in (("routed", True), ("dense", False)):
        cfg_i = base.with_(use_routed_moe=routed)
        fwd = partial(forward, cfg=cfg_i)

        @partial(jax.jit, donate_argnums=(2, 3))
        def pre(params, tokens, k, v):
            logits, k, v = fwd(
                params, tokens=tokens, k_cache=k, v_cache=v,
                start_pos=jnp.zeros((tokens.shape[0],), jnp.int32),
                logit_positions=jnp.full((tokens.shape[0],), tokens.shape[1] - 1,
                                         jnp.int32),
                fresh_prefill=True,
            )
            return logits, k, v

        toks = jnp.ones((batch, long_t), jnp.int32)
        k, v = make_cache(base, batch, long_t)
        logits, k, v = pre(params, toks, k, v)
        _sync(logits)
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            logits, k, v = pre(params, toks, k, v)
            _sync(logits)
            best = min(best, time.perf_counter() - t0)
        ab[nm] = round(best, 4)
        del k, v, logits
        gc.collect()
    out["prefill_deep"] = {
        "tokens": batch * long_t, **ab,
        "routed_speedup": round(ab["dense"] / ab["routed"], 3),
    }

    # small-batch decode (VERDICT r4 weak #4 follow-up): at b32, top-2-of-8
    # activates every expert and routed buys nothing at decode; b <= 4 is
    # where sparse routing can skip expert weight reads on ONE chip. Also
    # report the measured capacity-overflow drop fraction (the exact
    # serving-path routing on sample activations) — the drop-rate stat the
    # r4 review asked for alongside the ablation.
    if os.environ.get("BENCH_MOE_SMALL", "1") != "0":
        from nats_llm_studio_tpu.parallel.moe import routed_drop_fraction

        small: dict = {"capacity_factor": base.moe_capacity_factor}
        for b in (1, 4):
            r = decode_bench(base.with_(use_routed_moe=True), params, b,
                             prompt_len, seq_len, steps)
            dn = decode_bench(base.with_(use_routed_moe=False), params, b,
                              prompt_len, seq_len, steps)
            small[f"b{b}"] = {
                "routed_tok_s": r["tok_s"],
                "dense_tok_s": dn["tok_s"],
                "routed_speedup": round(r["tok_s"] / dn["tok_s"], 3),
            }
        blk0 = jax.tree.map(lambda a: a[0], params["blocks"])
        key = jax.random.PRNGKey(11)
        drops = {}
        for shape_name, shp in (("decode_b1", (1, 1)), ("decode_b4", (4, 1)),
                                ("decode_b32", (32, 1)),
                                ("prefill_4x128", (4, 128))):
            x = jax.random.normal(
                jax.random.fold_in(key, len(drops)),
                (*shp, base.d_model), jnp.dtype(base.dtype),
            )
            drops[shape_name] = round(routed_drop_fraction(
                x, blk0, base, base.moe_capacity_factor), 4)
        small["drop_fraction"] = drops
        out["small_batch"] = small

    del params
    gc.collect()
    return out


# ---------------------------------------------------------------------------
# long-context prefill (single-dispatch flash kernel, SURVEY §5)
# ---------------------------------------------------------------------------


def long_prefill_bench(cfg, params, T: int) -> dict:
    cfg = cfg.with_(max_seq_len=max(cfg.max_seq_len, T),
                    use_flash_attention=jax.default_backend() == "tpu")
    fwd = partial(forward, cfg=cfg)

    @partial(jax.jit, donate_argnums=(2, 3))
    def prefill(params, tokens, k, v, start):
        logits, k, v = fwd(
            params, tokens=tokens, k_cache=k, v_cache=v, start_pos=start,
            logit_positions=jnp.full((1,), tokens.shape[1] - 1, jnp.int32),
            fresh_prefill=True,
        )
        return logits[:, -1, :], k, v

    tokens = jnp.ones((1, T), jnp.int32)
    start = jnp.zeros((1,), jnp.int32)
    k, v = make_cache(cfg, 1, T)
    logits, k, v = prefill(params, tokens, k, v, start)  # compile
    _sync(logits)
    t0 = time.perf_counter()
    logits, k, v = prefill(params, tokens, k, v, start)
    _sync(logits)
    dt = time.perf_counter() - t0
    del k, v, logits
    gc.collect()
    return {"tokens": T, "seconds": round(dt, 3), "tok_s": round(T / dt, 1)}


# ---------------------------------------------------------------------------
# end-to-end over the NATS wire (BASELINE.md's metric definition)
# ---------------------------------------------------------------------------


# the README example payload is a short single-turn chat (~15 prompt tokens,
# /root/reference/README.md:227-230 usage block) — BASELINE.md config 2's
# "chat_model req-reply (README example payload)" is measured with this shape
SHORT_PROMPT = "Hello! Introduce yourself briefly."
# ~120 tokens — a heavier-payload honesty check, NOT long context (the r3
# artifact mislabeled this wave "long_prompt"; true long-context serving is
# measured by e2e_long_context_bench with >= 4096 REAL prompt tokens)
MEDIUM_PROMPT = "benchmark prompt: " + "tell me about tensor processing units. " * 3


def make_long_prompt(n_tokens: int) -> str:
    """~n_tokens ASCII chars: the bench tokenizer is byte-level BPE with no
    merges, so every ASCII character is exactly one token (the response's
    usage.prompt_tokens confirms the count in the artifact)."""
    base = "the quick brown fox jumps over the lazy dog near the river bank. "
    return (base * (n_tokens // len(base) + 1))[:n_tokens]


def _make_bench_tokenizer(cfg):
    from nats_llm_studio_tpu.gguf.tokenizer import GGUFTokenizer, _byte_to_unicode

    b2u = _byte_to_unicode()
    vocab = [b2u[i] for i in range(256)]
    vocab += [f"<filler_{i}>" for i in range(cfg.vocab_size - 257)]
    vocab.append("<|eot|>")
    return GGUFTokenizer(
        "gpt2", vocab, merges=[], eos_id=cfg.vocab_size - 1, add_bos=False
    )


def _pctl(sorted_vals, q: float) -> float:
    """Percentile over an ASCENDING-sorted list (0.0 for empty) — the one
    index rule every CLIENT-SIDE reported p50/p95 shares. Batcher-side
    percentiles come from obs.LogHistogram snapshots instead."""
    if not sorted_vals:
        return 0.0
    return sorted_vals[min(len(sorted_vals) - 1, int(len(sorted_vals) * q))]


def _phase_hists(batcher) -> dict:
    """Snapshot every batcher histogram at a phase boundary (for delta)."""
    return {name: h.snapshot() for name, h in batcher.stats.histograms().items()}


def _phase_delta(batcher, s0: dict, h0: dict) -> dict:
    """Batcher counters for ONE measured phase (difference against the
    snapshot taken before it) — the r3 artifact's tokens_per_step_avg mixed
    warmup and every phase into one cumulative number, hiding the
    throughput phase's true occupancy. ``h0`` holds the phase-start
    ``HistSnapshot`` per histogram (see ``_phase_hists``); subtracting
    snapshots isolates each phase's distribution without any deque replay."""
    s1 = batcher.stats.snapshot()
    h1 = _phase_hists(batcher)
    delays = h1["admit_queue_delay_ms"] - h0["admit_queue_delay_ms"]
    steps = s1["decode_steps"] - s0["decode_steps"]
    toks = s1["tokens"] - s0["tokens"]
    out = {
        "tokens": toks,
        "decode_steps": steps,
        "tokens_per_step_avg": round(toks / steps, 2) if steps else 0.0,
        "admit_queue_delay_p50_ms": round(delays.percentile(0.5), 1),
        "admit_queue_delay_p95_ms": round(delays.percentile(0.95), 1),
    }
    for name in ("ttft_ms", "decode_step_ms"):
        d = h1[name] - h0[name]
        if d.count:
            out[f"batcher_{name[:-3]}_p50_ms"] = round(d.percentile(0.5), 1)
            out[f"batcher_{name[:-3]}_p95_ms"] = round(d.percentile(0.95), 1)
    return out


def e2e_nats_bench(cfg, params, model_id: str, clients_a: int = 8,
                   clients_b: int = 96) -> dict:
    """Embedded broker + worker + real engine, driven via
    ``lmstudio.chat_model`` request/stream over the NATS wire.

    Measured phases on one serving stack (96 slots — int8 KV halves
    per-slot cache so the serving batch rides the same b96 capacity
    frontier the device-scan headline uses):
      A.  8 concurrent clients, README-shaped short prompts -> TTFT p50/p95
          (the BASELINE config-2 latency bar),
      B.  96 concurrent clients x 128 tokens, one synchronized wave ->
          aggregate served tok/s (the ramp-dominated worst case),
      B2. the same width CLOSED-LOOP (each client sends its next request
          the moment the previous completes, 2 rounds) -> sustained tok/s,
          the steady state a deployed worker actually sees,
      C.  8 clients, ~140-token prompts -> heavier-payload honesty check.

    The warmup covers every program the measured phases reach: singleton
    admits at both prompt buckets, group-admit widths (mpad 2..32), and
    every decode-window bucket (round-2 advisor: a fresh compile inside
    the timed phase skews TTFT p95).
    """
    import asyncio

    from nats_llm_studio_tpu.serve.batcher import ContinuousBatcher

    tokenizer = _make_bench_tokenizer(cfg)
    slots = int(os.environ.get("BENCH_E2E_SLOTS", str(max(clients_a, clients_b))))
    # wide group admits: a 96-client wave rides 3 pipelined [32, bucket]
    # prefills instead of 12 [8, *] — the dominant term in wave ramp time
    # (the served/device gap, VERDICT r3 weak #1) and in TTFT p95 under
    # load (missing #4)
    group = int(os.environ.get("BENCH_GROUP", "32"))
    # burst 16 (vs the batcher's 8): halves the per-burst host/dispatch
    # fixed cost per step; 32 idles completed slots a whole burst before
    # readmission. Chosen before PR 21 — not re-measured on a local chip
    burst = int(os.environ.get("BENCH_BURST", "16"))
    # coalesce 15 ms (vs the 3 ms default): a synchronized 96-client wave
    # trickles through the broker over tens of ms — eagerly admitting the
    # first handful as a narrow group wastes the wide-admit programs on
    # small MXU tiles; the wider window costs 15 ms of TTFT floor and
    # buys back most of the ramp
    coalesce = float(os.environ.get("BENCH_COALESCE_MS", "15"))
    batcher = ContinuousBatcher(
        params, cfg, max_slots=slots, max_seq_len=512,
        buckets=[64, 256, 512], max_group_admit=group, decode_burst=burst,
        admit_coalesce_ms=coalesce,
    )

    async def body(nc, one_chat):
        async def wave(n: int, prompt: str, max_tokens: int, base_tag: int,
                       rounds: int = 1):
            """``rounds`` > 1 = CLOSED-LOOP clients: each sends its next
            request the moment the previous completes, so admits stagger
            naturally against decode instead of arriving as one
            synchronized ramp — the steady state a deployed worker
            actually sees (the reference's clients are independent
            services, /root/reference/README.md:508-562)."""
            s0 = batcher.stats.snapshot()
            d0 = _phase_hists(batcher)

            async def client(i: int):
                out = []
                for r in range(rounds):
                    tag = base_tag + rounds * i + r
                    out.append(await one_chat(tag, f"{prompt} [{tag}]",
                                              max_tokens))
                return out

            t0 = time.perf_counter()
            per_client = await asyncio.gather(*(client(i) for i in range(n)))
            wall = time.perf_counter() - t0
            results = [r for rs in per_client for r in rs]
            ttfts = sorted(r["ttft_s"] * 1e3 for r in results
                           if r["ttft_s"] == r["ttft_s"])
            toks = sum(r["completion_tokens"] for r in results)
            return {
                "ttft_p50_ms": round(_pctl(ttfts, 0.5), 1),
                "ttft_p95_ms": round(_pctl(ttfts, 0.95), 1),
                "tok_s": round(toks / wall, 1),
                "clients": n,
                "max_tokens": max_tokens,
                "requests": len(results),
                "parse_failures": sum(1 for r in results if r["parse_fail"]),
                "batcher_phase": _phase_delta(batcher, s0, d0),
            }

        # compile warmup: single admit at BOTH prompt buckets (a straggler
        # outside its wave's group takes the singleton admit_fused path —
        # unwarmed, its compile lands in the measured p95), every
        # group-admit width the waves can reach (mpad 2..max_group_admit),
        # and every decode window the phases sweep the ring across
        # (64/256/None)
        await one_chat(0, SHORT_PROMPT, 16)
        await one_chat(1, MEDIUM_PROMPT, 16)
        w = 2
        while w <= min(batcher.max_group_admit, max(clients_a, clients_b)):
            await asyncio.gather(
                *(one_chat(100 * w + i, SHORT_PROMPT, 16) for i in range(w))
            )
            w *= 2
        # medium-prompt warmup across group widths, REPEATED: arrival
        # timing can split a warmup gather into smaller groups (e.g. 4+4),
        # leaving a bucket-256 admit width uncompiled — one run measured a
        # flat 6.6 s compile inside the medium wave from exactly this.
        # Two passes over widths {2, 4, 8} make a missed mpad vanishingly
        # unlikely.
        for rep in range(2):
            w = 2
            while w <= min(8, clients_a):
                await asyncio.gather(
                    *(one_chat(900 + 100 * rep + 10 * w + i, MEDIUM_PROMPT, 16)
                      for i in range(w))
                )
                w *= 2
        # drive the ring past the last window bucket once: closed-loop
        # rounds (no cold reset between a client's requests) push the
        # shared ring head past 248 where decode switches to the
        # full-window (None) program — a distinct compile that must not
        # land inside the measured sustained wave
        await one_chat(990, SHORT_PROMPT, 250)

        # drain between waves: the depth-2 pipeline leaves one zombie
        # burst in flight after a wave's last stream ends; a new wave's
        # admits queueing behind its readback would charge ~a burst +
        # round trip (~190 ms measured) to TTFT that no steady-state
        # request pays
        await asyncio.sleep(0.75)
        a = await wave(clients_a, SHORT_PROMPT, 32, base_tag=1000)
        await asyncio.sleep(0.75)
        b = await wave(clients_b, SHORT_PROMPT, 128, base_tag=2000)
        await asyncio.sleep(0.75)
        # rounds=3 (vs 2 in r4): the first round is a synchronized cold
        # ramp; more rounds measure more of the actual steady state the
        # phase exists to report (the ramp's share drops from ~1/5 to ~1/8)
        b2 = await wave(clients_b, SHORT_PROMPT, 128, base_tag=20000,
                        rounds=int(os.environ.get("BENCH_SUSTAINED_ROUNDS", "3")))
        await asyncio.sleep(0.75)
        # 256-token streams: the decode floor dominates and the fixed wave
        # edges (ramp + final-readback sync) amortize — the regime
        # sustained serving actually runs in. The
        # 128-token wave above stays for round-3 comparability.
        b3 = await wave(clients_b, SHORT_PROMPT, 256, base_tag=40000)
        await asyncio.sleep(0.75)
        c = await wave(clients_a, MEDIUM_PROMPT, 32, base_tag=4000)
        await asyncio.sleep(0.75)

        # -- ring-compaction-under-load phase (VERDICT r4 weak #5) ----------
        # One stream drives the shared 512-ring head near wrap, a second
        # joins late with a small position, the first ends -> the ring wraps
        # while the survivor is live -> maybe_compact() re-rolls. Run TWICE:
        # rep 0 compiles the compact program + post-roll windows, rep 1 is
        # the measured recovery. The survivor's inter-chunk gaps split at
        # the roll timestamp quantify windowed-read recovery.
        async def ring_phase(base_tag: int) -> dict:
            s0 = batcher.stats.snapshot()
            d0 = _phase_hists(batcher)
            gaps: list[tuple[float, float]] = []
            roll_t: float | None = None

            async def poll_roll():
                nonlocal roll_t
                while roll_t is None:
                    if batcher.stats.ring_compactions > s0["ring_compactions"]:
                        roll_t = time.perf_counter()
                        return
                    await asyncio.sleep(0.02)

            poller = asyncio.create_task(poll_roll())
            t0 = time.perf_counter()
            # driver: decodes until the 512-ring's length cap (~pos 505+)
            driver = asyncio.create_task(
                one_chat(base_tag, SHORT_PROMPT, 430)
            )
            # survivor joins LATE (driver ~70 steps from its cap) so at the
            # wrap its own position is small — maybe_compact() rolls only
            # when the live window bucket is <= max_seq/2, and the late
            # join leaves ~30 post-roll bursts to measure
            while (batcher.stats.tokens
                   - s0["tokens"]) < 360 and not driver.done():
                await asyncio.sleep(0.02)
            surv = await one_chat(base_tag + 1, SHORT_PROMPT, 320, gaps=gaps)
            drv = await driver
            poller.cancel()
            wall = time.perf_counter() - t0
            phase = _phase_delta(batcher, s0, d0)
            rolls = batcher.stats.ring_compactions - s0["ring_compactions"]
            pre = sorted(g * 1e3 for t, g in gaps
                         if roll_t is None or t < roll_t)
            post = sorted(g * 1e3 for t, g in gaps
                          if roll_t is not None and t >= roll_t)
            return {
                "ring_compactions": rolls,
                "survivor_gap_pre_roll_p50_ms": round(_pctl(pre, 0.5), 1),
                "survivor_gap_post_roll_p50_ms": round(_pctl(post, 0.5), 1),
                "gap_samples_pre": len(pre),
                "gap_samples_post": len(post),
                "driver_tokens": drv["completion_tokens"],
                "survivor_tokens": surv["completion_tokens"],
                "wall_s": round(wall, 2),
                "parse_failures": int(drv["parse_fail"]) + int(surv["parse_fail"]),
                "batcher_phase": phase,
            }

        await ring_phase(base_tag=6000)  # compile rep (compact_ring + windows)
        await asyncio.sleep(0.75)
        ring = await ring_phase(base_tag=6100)
        await asyncio.sleep(0.75)

        # -- sustained-overload phase (VERDICT r4 missing #2 measurement) ---
        # 1.5x slots closed-loop clients against a 2 s admit-age bound:
        # requests that cannot be served within the bound get an immediate
        # honest shed reply and the client retries after a short backoff
        # (modeling the bus handing it to a queue-group peer). Replaces the
        # r4 silent 38.6 s admit-delay tail with a bounded p95 + an
        # explicit shed count. Prior bounds are restored afterwards.
        async def overload_phase(n_clients: int, rounds: int,
                                 base_tag: int) -> dict:
            prev_age, prev_queue = batcher.max_queue_age_ms, batcher.max_queue
            batcher.max_queue_age_ms = float(
                os.environ.get("BENCH_SHED_AGE_MS", "2000"))
            batcher.max_queue = int(
                os.environ.get("BENCH_SHED_QUEUE", str(4 * batcher.max_slots)))
            s0 = batcher.stats.snapshot()
            d0 = _phase_hists(batcher)
            bo = getattr(batcher, "brownout", None)
            bo_trans0 = bo.transitions if bo is not None else 0
            aborted0 = batcher.stats.cancel_causes.get("deadline", 0)
            try:
                async def client(i: int):
                    completed = sheds = other = toks = abandoned = 0
                    ttfts_c = []
                    for r in range(rounds):
                        tag = base_tag + 16 * (rounds * i + r)
                        for attempt in range(8):
                            res = await one_chat(tag + attempt,
                                                 f"{SHORT_PROMPT} [{i}.{r}]", 128)
                            if not res["parse_fail"]:
                                completed += 1
                                toks += res["completion_tokens"]
                                if res["ttft_s"] == res["ttft_s"]:
                                    ttfts_c.append(res["ttft_s"])
                                break
                            err = res.get("error") or ""
                            if "shed" in err or "overloaded" in err or "full" in err:
                                sheds += 1
                                await asyncio.sleep(0.25)  # retry (peer analog)
                            else:
                                other += 1
                                break
                        else:  # shed on every attempt: the round is ABANDONED
                            abandoned += 1  # keeps completed+other+abandoned
                            # == rounds so the accounting always balances
                    return completed, sheds, other, ttfts_c, toks, abandoned

                t0 = time.perf_counter()
                per = await asyncio.gather(*(client(i) for i in range(n_clients)))
                wall = time.perf_counter() - t0
            finally:
                batcher.max_queue_age_ms = prev_age
                batcher.max_queue = prev_queue
            phase = _phase_delta(batcher, s0, d0)
            completed = sum(p[0] for p in per)
            sheds_seen = sum(p[1] for p in per)
            other = sum(p[2] for p in per)
            ttfts = sorted(t * 1e3 for p in per for t in p[3])
            total_toks = sum(p[4] for p in per)
            abandoned = sum(p[5] for p in per)
            return {
                "clients": n_clients,
                "rounds": rounds,
                "abandoned_rounds": abandoned,
                "slots": batcher.max_slots,
                "admit_age_bound_ms": float(
                    os.environ.get("BENCH_SHED_AGE_MS", "2000")),
                "admit_queue_bound": int(
                    os.environ.get("BENCH_SHED_QUEUE", str(4 * batcher.max_slots))),
                "completed": completed,
                "sheds_observed_by_clients": sheds_seen,
                "other_errors": other,
                "batcher_shed_total": batcher.stats.shed - s0["shed"],
                # deadline/brownout phase deltas (ISSUE 5): how much of the
                # shedding was deadline-driven and whether the controller
                # actually browned out during the storm
                "deadline_shed": (
                    batcher.stats.shed_cause_counts().get("deadline", 0)
                    - (s0.get("shed_causes") or {}).get("deadline", 0)
                ),
                "deadline_aborted": (
                    batcher.stats.cancel_causes.get("deadline", 0) - aborted0
                ),
                "brownout_level": getattr(batcher, "brownout_level", 0),
                "brownout_transitions": (
                    (bo.transitions - bo_trans0) if bo is not None else 0
                ),
                "served_tok_s": round(total_toks / wall, 1),
                "ttft_p50_ms": round(_pctl(ttfts, 0.5), 1),
                "ttft_p95_ms": round(_pctl(ttfts, 0.95), 1),
                "wall_s": round(wall, 2),
                "batcher_phase": phase,  # admit delay p95 <= the age bound
            }

        overload = await overload_phase(
            n_clients=int(os.environ.get("BENCH_SHED_CLIENTS",
                                         str(3 * clients_b // 2))),
            rounds=2, base_tag=60000,
        )
        return a, b, b2, b3, c, ring, overload

    a, b, b2, b3, c, ring, overload = _drive_engine(
        cfg, params, model_id, tokenizer, batcher, body)

    # TTFT pays two dispatch + readback round trips (launch ack,
    # first-token readback). Measure the noop round trip and report it
    # next to the TTFT it is part of.
    noop = jax.jit(lambda x: x + 1)
    z = jnp.zeros((8,), jnp.int32)
    np.asarray(noop(z))
    rts = []
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(noop(z))
        rts.append(time.perf_counter() - t0)
    rt_ms = round(1e3 * sorted(rts)[1], 1)

    return {
        # flat headline keys, each labeled with ITS measurement's
        # concurrency (phase A latency, phase B throughput)
        "ttft_p50_ms": a["ttft_p50_ms"],  # config-2 latency bar, phase A
        "ttft_p95_ms": a["ttft_p95_ms"],
        "ttft_clients": a["clients"],
        "e2e_tok_s": b["tok_s"],  # served throughput, phase B (128-tok, r3-comparable)
        "e2e_tok_s_clients": b["clients"],
        "e2e_sustained_tok_s": b2["tok_s"],  # closed-loop, phase B2
        "e2e_tok_s_256": b3["tok_s"],  # 256-token streams, phase B3
        "transport_rt_ms": rt_ms,
        "ttft_p50_net_of_transport_ms": round(
            max(0.0, a["ttft_p50_ms"] - 2 * rt_ms), 1
        ),
        "short_wave": a,
        "throughput_wave": b,
        "sustained_wave": b2,
        "long_stream_wave": b3,
        "medium_prompt_wave": c,
        "ring_compaction": ring,
        "overload": overload,
        # CUMULATIVE run-wide counters (warmup + every phase above),
        # marked as such. Latency percentiles are deliberately absent:
        # a run-wide histogram folds the warmup ramp and all seven phases'
        # admit-delay samples into one distribution that contradicts every
        # per-phase number (the r05 artifact's cumulative admit p95 read
        # 6.9 s against a 38 ms throughput-wave delta) — each phase's
        # ``batcher_phase`` delta block is the authoritative latency
        # record; this block is for conservation checks only (sheds +
        # completions + cancels must balance across phases).
        "batcher": {
            "scope": "cumulative_counters",
            **batcher.stats.counters(),
            "peak_active_slots": batcher.stats.peak_active,
            "shed_causes": batcher.stats.shed_cause_counts(),
        },
    }


# ---------------------------------------------------------------------------
# long-context SERVING (VERDICT r3 missing #1): >= 4096 REAL prompt tokens
# through lmstudio.chat_model with chunked prefill, measured end-to-end
# ---------------------------------------------------------------------------


def _drive_engine(cfg, params, model_id, tokenizer, batcher, body_fn):
    """Stand up broker+worker+engine around ``batcher``, run ``body_fn``
    (async, given a connected client and a one_chat helper), tear down."""
    import asyncio

    from nats_llm_studio_tpu.config import WorkerConfig
    from nats_llm_studio_tpu.serve import Worker
    from nats_llm_studio_tpu.serve.api import ModelNotFound, Registry
    from nats_llm_studio_tpu.serve.registry import JaxChatEngine
    from nats_llm_studio_tpu.transport import EmbeddedBroker, connect

    engine = JaxChatEngine(model_id, batcher, tokenizer, cfg, meta={})

    class Preloaded(Registry):
        async def list_models(self):
            return {"object": "list", "data": [engine.info()]}

        async def pull(self, identifier):
            raise ModelNotFound(identifier)

        async def delete(self, model_id_):
            raise ModelNotFound(model_id_)

        async def get_engine(self, mid):
            if mid != model_id:
                raise ModelNotFound(mid)
            return engine

        async def sync_from_bucket(self, name, model_id=None):
            raise ModelNotFound(name)

        def stats(self):
            return {"models_loaded": [model_id]}

        def loaded_engines(self):
            # base Registry returns {} — expose the engine so the worker's
            # Prometheus exposition renders its per-model rows (the prefix
            # phase asserts hit counters off the wire, not in-process)
            return {model_id: engine}

    async def drive():
        broker = await EmbeddedBroker().start()
        worker = Worker(WorkerConfig(nats_url=broker.url), Preloaded())
        await worker.start()
        nc = await connect(broker.url)

        async def one_chat(tag: int, prompt: str, max_tokens: int,
                           gaps: list | None = None,
                           temperature: float = 0.8):
            body = json.dumps(
                {
                    "model": model_id,
                    "messages": [{"role": "user", "content": prompt}],
                    "max_tokens": max_tokens,
                    "temperature": temperature,
                    "seed": tag,
                    "stream": True,
                }
            ).encode()
            t0 = time.perf_counter()
            ttft = None
            prev = t0
            n_tok = prompt_toks = 0
            parse_fail = False
            error = None
            async for msg in nc.request_stream(
                "lmstudio.chat_model", body, timeout=1800.0, idle_timeout=900.0
            ):
                now = time.perf_counter()
                if (msg.headers or {}).get("Nats-Stream-Done") is not None:
                    try:
                        done = json.loads(msg.payload)
                        usage = done["data"]["response"]["usage"]
                        n_tok = usage["completion_tokens"]
                        prompt_toks = usage["prompt_tokens"]
                    except Exception:  # noqa: BLE001 — error envelope
                        parse_fail = True
                        try:  # keep the envelope's error string (shed vs other)
                            error = json.loads(msg.payload).get("error")
                        except Exception:  # noqa: BLE001
                            pass
                    break
                if ttft is None:
                    ttft = now - t0
                elif gaps is not None:
                    gaps.append((now, now - prev))  # (timestamp, inter-chunk gap)
                prev = now
            return {
                "ttft_s": ttft if ttft is not None else float("nan"),
                "wall_s": time.perf_counter() - t0,
                "completion_tokens": n_tok,
                "prompt_tokens": prompt_toks,
                "parse_fail": parse_fail,
                "error": error,
            }

        try:
            return await body_fn(nc, one_chat)
        finally:
            for step in (nc.close, worker.drain, broker.stop,
                         lambda: asyncio.to_thread(batcher.stop)):
                try:
                    await step()
                except Exception:  # noqa: BLE001 — best-effort teardown
                    pass

    return asyncio.run(drive())


def e2e_long_context_bench(cfg, params, model_id: str, n_long: int = 4,
                           long_tokens: int = 4200, xl_tokens: int = 7936) -> dict:
    """Long-context serving measured end-to-end, in TWO engines sized to the
    chip (the AOT compile path double-counts the donated KV cache, so an 8k
    ring affords ~3 slots next to 8.7 GB of int8 weights — a 4.6k ring
    affords 8):

    * wave engine (max_seq 4608): ``n_long`` concurrent clients each send a
      >= 4096-token prompt (full-history resend is the reference product's
      steady state, /root/reference/README.md:196-205) while 2 short
      streams decode throughout — their inter-chunk gap p95 bounds the
      stall chunked admission imposes on live streams;
    * XL engine (max_seq 8192, 2 slots): one ``xl_tokens`` prompt alone —
      the 8k-class point.

    Token counts are read back from usage.prompt_tokens (byte-level
    tokenizer: 1 ASCII char = 1 token), not assumed."""
    import asyncio

    from nats_llm_studio_tpu.serve.batcher import ContinuousBatcher

    tokenizer = _make_bench_tokenizer(cfg)
    wave_seq = int(os.environ.get("BENCH_LONG_SEQ", "4608"))
    slots = int(os.environ.get("BENCH_LONG_SLOTS", str(n_long + 2)))
    chunk = int(os.environ.get("BENCH_LONG_CHUNK", "512"))
    if wave_seq >= 4608:  # tiny smoke runs shrink everything via env
        assert long_tokens >= 4096, "the wave must carry >=4k-token prompts"

    coalesce = float(os.environ.get("BENCH_COALESCE_MS", "15"))
    wave_batcher = ContinuousBatcher(
        params, cfg, max_slots=slots, max_seq_len=wave_seq,
        buckets=[b for b in (512, 1024, 2048) if b < wave_seq] + [wave_seq],
        prefill_chunk=chunk, admit_coalesce_ms=coalesce,
    )

    async def wave_body(nc, one_chat):
        # warmup: compiles the singleton [1, chunk] prefill + finish, the
        # BATCHED chunked-admit programs at widths 2 and 4 ([m, chunk]
        # chunks + finish_admit_group), the short-prompt admit, and the
        # decode windows the measured phase reaches — all outside the
        # timed window
        # prompt lengths CLAMPED below the ring so env-shrunk smoke configs
        # (BENCH_LONG_SEQ=256) don't silently discard the warmup as
        # too-long errors and push the compiles into the measured window
        wlen = min(chunk + 256, wave_seq - 64)
        wlen2 = min(chunk + 300, wave_seq - 48)
        # deterministic chunk-program warmup FIRST: every (width, window)
        # chunked-prefill program, compiled directly — the pow2 window
        # ladder multiplied the program count, and chat-driven warmup
        # coverage races on arrival timing (a missed pair lands a
        # multi-second compile inside the measured TTFT; seen as the
        # 5.2 s long-wave TTFT in the r5 iteration runs)
        await asyncio.to_thread(wave_batcher.warm_chunk_programs)
        # solo short + short pair: the measured phase starts with 2
        # interference shorts decoding alone at a COLD ring — that is the
        # smallest decode window and the mpad-2 group admit, two programs
        # none of the long warmups reach (the long note_admit wraps the
        # ring -> full-window decode). The r4-f compile log caught an
        # 11 s decode compile inside the measured wave from exactly this.
        await one_chat(30, SHORT_PROMPT, 24)
        await asyncio.gather(
            one_chat(31, SHORT_PROMPT, 24), one_chat(32, SHORT_PROMPT, 24)
        )
        await one_chat(0, make_long_prompt(wlen), 8)
        await asyncio.gather(
            one_chat(1, SHORT_PROMPT, 8),
            *(one_chat(2 + i, make_long_prompt(wlen2), 8) for i in range(2)),
        )
        # solo long at the TOP bucket: the singleton finish/decode programs
        # at the wave_seq bucket are otherwise first compiled INSIDE the
        # measured wave whenever one long straggles behind the group admit
        # (coalesce is only 15 ms)
        await one_chat(4, make_long_prompt(long_tokens), 8)
        # TWO passes at full width: a split warmup gather (e.g. 2+2) would
        # leave the width-4 chunk/finish programs uncompiled and their
        # ~20 s compile would land inside the measured wave (seen once in
        # the r4 iteration runs)
        for rep in range(2):
            await asyncio.gather(
                *(one_chat(5 + 10 * rep + i, make_long_prompt(long_tokens), 8)
                  for i in range(4))
            )
        await asyncio.sleep(0.75)  # drain in-flight zombie bursts

        # measured: 2 short interference streams decode while n_long long
        # prompts chunk-prefill through the same batcher
        s0 = wave_batcher.stats.snapshot()
        d0 = _phase_hists(wave_batcher)
        gaps: list[float] = []
        t0 = time.perf_counter()
        short_tasks = [
            asyncio.create_task(one_chat(10 + i, SHORT_PROMPT, 160, gaps=gaps))
            for i in range(2)
        ]
        await asyncio.sleep(0.3)  # shorts admitted + decoding first
        t_longs = time.perf_counter()
        longs = await asyncio.gather(
            *(one_chat(100 + i, make_long_prompt(long_tokens), 32)
              for i in range(n_long))
        )
        # prefill throughput over the LONGS' own window (send -> all four
        # complete); the full-wave wall below additionally waits for the
        # interference shorts' 160-token decode, which would otherwise
        # deflate "prefill" with unrelated decode time
        longs_wall = time.perf_counter() - t_longs
        shorts = await asyncio.gather(*short_tasks)
        wall = time.perf_counter() - t0
        phase = _phase_delta(wave_batcher, s0, d0)

        ttfts = sorted(r["ttft_s"] * 1e3 for r in longs if r["ttft_s"] == r["ttft_s"])
        gap_ms = sorted(g * 1e3 for _, g in gaps)
        total_prefill_toks = sum(r["prompt_tokens"] for r in longs)
        total_out = sum(r["completion_tokens"] for r in list(longs) + list(shorts))
        return {
            "clients": n_long,
            "prompt_tokens_each": longs[0]["prompt_tokens"],
            "ttft_p50_ms": round(_pctl(ttfts, 0.5), 1),
            "ttft_max_ms": round(ttfts[-1], 1) if ttfts else 0.0,
            "prefill_tok_s": round(total_prefill_toks / longs_wall, 1),
            "wave_tok_s": round(total_out / wall, 1),
            "parse_failures": sum(1 for r in list(longs) + list(shorts)
                                  if r["parse_fail"]),
            "interference_gap_p50_ms": round(_pctl(gap_ms, 0.5), 1),
            "interference_gap_p95_ms": round(_pctl(gap_ms, 0.95), 1),
            "batcher_phase": phase,
            "max_seq_len": wave_seq,
            "prefill_chunk": chunk,
            "slots": slots,
        }

    long_wave = _drive_engine(cfg, params, model_id, tokenizer, wave_batcher,
                              wave_body)
    gc.collect()

    def xl_point(xl_seq: int, n_tokens: int) -> dict:
        """One N-token prompt served alone on a 2-slot engine with an
        xl_seq ring (2 slots x 16k int8 KV ~ 2.2 GB next to 8.7 GB int8
        weights — inside the AOT double-count budget). The model config's
        context length is raised to the ring size: ContinuousBatcher clamps
        max_seq_len to cfg.max_seq_len, which silently rejected 16k prompts
        on the 8k-configured 8B geometry."""
        xl_cfg = cfg.with_(max_seq_len=max(cfg.max_seq_len, xl_seq))
        xl_batcher = ContinuousBatcher(
            params, xl_cfg, max_slots=2, max_seq_len=xl_seq,
            buckets=[b for b in (512, 2048) if b < xl_seq] + [xl_seq],
            prefill_chunk=1024,
        )

        async def xl_body(nc, one_chat):
            # every chunk window's program, compiled deterministically (the
            # pow2 ladder is 4-5 programs at 8-16k; an unwarmed one's
            # multi-second compile would land inside the measured TTFT),
            # then one chat to warm admit/finish/decode programs
            await asyncio.to_thread(xl_batcher.warm_chunk_programs, (1,))
            await one_chat(0, make_long_prompt(1536), 8)
            # full-length pass: warms the measured request's own full-window
            # decode program too (post-TTFT, but keeps wall honest)
            await one_chat(1, make_long_prompt(n_tokens), 8)
            xl = await one_chat(500, make_long_prompt(n_tokens), 32)
            return {
                "prompt_tokens": xl["prompt_tokens"],
                "ttft_ms": round(xl["ttft_s"] * 1e3, 1),
                "prefill_tok_s": (
                    round(xl["prompt_tokens"] / xl["ttft_s"], 1)
                    if xl["ttft_s"] == xl["ttft_s"] and xl["ttft_s"] > 0 else 0.0
                ),
                "completion_tokens": xl["completion_tokens"],
                "parse_fail": xl["parse_fail"],
                "max_seq_len": xl_seq,
            }

        out = _drive_engine(xl_cfg, params, model_id, tokenizer, xl_batcher,
                            xl_body)
        gc.collect()
        return out

    xl_single = xl_point(int(os.environ.get("BENCH_XL_SEQ", "8192")), xl_tokens)
    result = {"long_wave": long_wave, "xl_single": xl_single}
    # the 16k-class point: the same context length long_prefill proves
    # on-device, SERVED through chat_model (skipped for env-shrunk smokes)
    if os.environ.get("BENCH_XL16", "1") != "0" and wave_seq >= 4608:
        result["xl16_single"] = xl_point(16384, 15872)
    return result


# ---------------------------------------------------------------------------
# prefix cache: shared-system-prompt serving, cache ON vs OFF
# ---------------------------------------------------------------------------


def prefix_cache_bench(cfg, params, model_id: str) -> dict:
    """Shared-system-prompt serving with the prefix KV cache ON vs OFF
    (serve/prefix_cache.py): the same sequential turn mix — a fixed
    multi-chunk "system prompt + history" resent with a fresh tail each
    turn, the reference product's steady state — served twice on
    identically-sized engines. ON must beat OFF on BOTH TTFT p50 and total
    prefill seconds (only the uncached suffix is prefilled on a hit). The
    worker's Prometheus exposition is scraped so the hit counter is proven
    on the wire, not just in-process."""
    import asyncio

    from nats_llm_studio_tpu.serve.batcher import ContinuousBatcher

    tokenizer = _make_bench_tokenizer(cfg)
    seq = int(os.environ.get("BENCH_PREFIX_SEQ", "4608"))
    chunk = int(os.environ.get("BENCH_PREFIX_CHUNK", "512"))
    slots = int(os.environ.get("BENCH_PREFIX_SLOTS", "4"))
    n_turns = int(os.environ.get("BENCH_PREFIX_TURNS", "6"))
    blocks = int(os.environ.get("BENCH_PREFIX_BLOCKS", "64"))
    # the shared prefix ends 17 tokens past a chunk edge, so every reuse is
    # a PARTIAL hit resuming mid-prompt (the common case: a resent history
    # rarely ends exactly on a block boundary)
    shared = make_long_prompt(min(5 * chunk, seq // 2) + 17)

    def run_mode(cache_blocks: int) -> dict:
        batcher = ContinuousBatcher(
            params, cfg, max_slots=slots, max_seq_len=seq,
            buckets=[b for b in (512, 1024, 2048) if b < seq] + [seq],
            prefill_chunk=chunk, prefix_cache_blocks=cache_blocks,
        )

        async def body(nc, one_chat):
            await asyncio.to_thread(batcher.warm_chunk_programs, (1,))
            warm = make_long_prompt(min(chunk + 300, seq - 64))
            await one_chat(900, warm, 8)
            if cache_blocks > 0:
                # resend: the repeat takes the HIT path, compiling the
                # cached-block write + suffix programs outside the window
                await one_chat(901, warm, 8)
            s0 = batcher.stats.snapshot()
            h0 = _phase_hists(batcher)
            t0 = time.perf_counter()
            turns = [
                await one_chat(1000 + i, f"{shared} [turn {i:03d}] reply now", 16)
                for i in range(n_turns)
            ]
            wall = time.perf_counter() - t0
            h1 = _phase_hists(batcher)
            phase = _phase_delta(batcher, s0, h0)
            prefill_s = (h1["prefill_ms"] - h0["prefill_ms"]).total / 1e3
            hit_total = 0.0
            prom_line = ""
            if cache_blocks > 0:
                try:
                    reply = await nc.request("lmstudio.metrics.prom", b"",
                                             timeout=30.0)
                    for ln in reply.payload.decode().splitlines():
                        if ln.startswith("lmstudio_prefix_cache_hit_tokens_total"):
                            prom_line = ln
                            hit_total = float(ln.rsplit(" ", 1)[-1])
                            break
                except Exception:  # noqa: BLE001 — exposition is best-effort
                    pass
            ttfts = sorted(r["ttft_s"] * 1e3 for r in turns
                           if r["ttft_s"] == r["ttft_s"])
            out = {
                "turns": n_turns,
                "prompt_tokens_each": turns[0]["prompt_tokens"],
                "ttft_p50_ms": round(_pctl(ttfts, 0.5), 1),
                "ttft_max_ms": round(ttfts[-1], 1) if ttfts else 0.0,
                "prefill_s": round(prefill_s, 3),
                "wall_s": round(wall, 2),
                "parse_failures": sum(1 for r in turns if r["parse_fail"]),
                "batcher_phase": phase,
            }
            pc = batcher.prefix_cache
            if pc is not None:
                out["cache"] = pc.stats()
                out["prom_hit_tokens_total"] = hit_total
                out["prom_line"] = prom_line
            return out

        out = _drive_engine(cfg, params, model_id, tokenizer, batcher, body)
        gc.collect()
        return out

    on = run_mode(blocks)
    off = run_mode(0)
    return {
        "max_seq_len": seq,
        "prefill_chunk": chunk,
        "shared_prefix_tokens": len(shared),
        "cache_on": on,
        "cache_off": off,
        "ttft_p50_speedup": (
            round(off["ttft_p50_ms"] / on["ttft_p50_ms"], 2)
            if on["ttft_p50_ms"] else 0.0
        ),
        "prefill_s_saved": round(off["prefill_s"] - on["prefill_s"], 3),
    }


def kv_tiering_bench(cfg, params, model_id: str, *, seq: int | None = None,
                     chunk: int | None = None, slots: int | None = None,
                     n_prompts: int | None = None,
                     max_new: int | None = None) -> dict:
    """Hierarchical KV tiers (serve/kv_tiers.py) under a working set that
    CANNOT fit the HBM prefix budget: ``n_prompts`` distinct multi-chunk
    documents, each served twice, against a prefix cache sized for ONE of
    them. With tiering ON, round-1 evictions demote to the host tier and
    round-2 admits promote back — prefix hit tokens and TTFT p50 must beat
    the tiering-OFF run (where round 2 re-prefills almost everything), with
    ZERO ``kv_pool``-cause sheds. A third engine built on the same spill
    store with no live donor then proves restart-with-warm-cache: its first
    repeat prompt scores nonzero hit tokens. Decode step p50 ON/OFF is
    reported as the demotion-overhead ratio."""
    import asyncio

    from nats_llm_studio_tpu.serve.batcher import ContinuousBatcher
    from nats_llm_studio_tpu.serve.kv_tiers import KVTierManager, MemorySpillStore

    tokenizer = _make_bench_tokenizer(cfg)
    seq = seq or int(os.environ.get("BENCH_KV_TIER_SEQ", "512"))
    chunk = chunk or int(os.environ.get("BENCH_KV_TIER_CHUNK", "64"))
    slots = slots or int(os.environ.get("BENCH_KV_TIER_SLOTS", "2"))
    n_prompts = n_prompts or int(os.environ.get("BENCH_KV_TIER_PROMPTS", "20"))
    max_new = max_new or int(os.environ.get("BENCH_KV_TIER_MAX_NEW", "8"))
    # the cache budget holds exactly ONE document's full chunks; the
    # working set is n_prompts documents — 10x+ the cacheable budget
    n_chunks = 2
    prompt_tokens = n_chunks * chunk + 17
    block_tokens = 16
    cache_blocks = n_chunks * (chunk // block_tokens)
    # pool: live slots + the cache budget + promotion scratch — tight
    # enough that swap-don't-shed matters, big enough that honest serving
    # never needs a kv_pool shed
    per_slot = -(-(prompt_tokens + max_new) // block_tokens)
    pool_blocks = slots * per_slot + 3 * cache_blocks + 2

    def doc(i: int) -> str:
        return (f"[doc {i:03d}] " + make_long_prompt(prompt_tokens))[:prompt_tokens]

    spill = MemorySpillStore()  # survives across the engines below

    def build(tier_on: bool) -> ContinuousBatcher:
        b = ContinuousBatcher(
            params, cfg, max_slots=slots, max_seq_len=seq,
            buckets=[x for x in (128, 256) if x < seq] + [seq],
            prefill_chunk=chunk, prefix_cache_blocks=cache_blocks,
            kv_block_tokens=block_tokens, kv_pool_blocks=pool_blocks,
        )
        if tier_on:
            # host budget 0 = spill-through: every demoted chunk goes
            # straight to the (in-process) Object Store, so the restart
            # sub-phase deterministically finds complete chains there.
            # Host-LRU behavior is pinned by tests/test_kv_tiers.py; this
            # phase measures the pool↔tier swap and the cold-tier restart.
            b.kv_tiers = KVTierManager(
                int(os.environ.get("BENCH_KV_TIER_HOST_BYTES", "0")),
                chunk_tokens=b.prefill_chunk, spill=spill,
                namespace="kv/bench", max_spill_objects=256,
            )
        return b

    def run_mode(tier_on: bool) -> dict:
        batcher = build(tier_on)

        async def body(nc, one_chat):
            await asyncio.to_thread(batcher.warm_chunk_programs, (1,))
            await one_chat(900, doc(999), max_new)
            rounds = []
            for rnd in (1, 2):
                if tier_on and rnd == 2:
                    # round-1 demotions must be durably in the spill store
                    # before the repeat wave tries to promote them back
                    await asyncio.to_thread(batcher.kv_tiers.flush)
                s0 = batcher.stats.snapshot()
                h0 = _phase_hists(batcher)
                hit0 = batcher.prefix_cache.hit_tokens
                t0 = time.perf_counter()
                reqs = [
                    await one_chat(rnd * 1000 + i, doc(i), max_new)
                    for i in range(n_prompts)
                ]
                wall = time.perf_counter() - t0
                ttfts = sorted(r["ttft_s"] * 1e3 for r in reqs
                               if r["ttft_s"] == r["ttft_s"])
                rounds.append({
                    "ttft_p50_ms": round(_pctl(ttfts, 0.5), 1),
                    "hit_tokens": batcher.prefix_cache.hit_tokens - hit0,
                    "wall_s": round(wall, 2),
                    "batcher_phase": _phase_delta(batcher, s0, h0),
                })
            sheds = dict(batcher.stats.shed_cause_counts())
            out = {
                "round1": rounds[0],
                "round2": rounds[1],
                "shed_by_cause": sheds,
                "pool": batcher.pool_stats(),
                "cache": batcher.prefix_cache.stats(),
            }
            tier = batcher.tier_stats()
            if tier is not None:
                out["tier"] = tier
            if tier_on:
                if sheds.get("kv_pool", 0):
                    raise RuntimeError(
                        f"tiering on but {sheds['kv_pool']} kv_pool sheds — "
                        "swap-don't-shed is broken"
                    )
                if not tier or tier.get("demoted_chunks", 0) <= 0:
                    raise RuntimeError("tiering on but nothing demoted under "
                                       "10x working-set pressure")
                if tier.get("promoted_chunks", 0) <= 0:
                    raise RuntimeError("tiering on but round 2 promoted "
                                       "nothing back from the host tier")
            return out

        out = _drive_engine(cfg, params, model_id, tokenizer, batcher, body)
        gc.collect()
        return out

    on = run_mode(True)
    off = run_mode(False)

    # -- restart-with-warm-cache: fresh engine, same spill store, NO donor --
    restart_b = build(True)
    restart_b.start()
    warm_tokens = 0
    for export in restart_b.kv_tiers.warm_exports(limit=4):
        warm_tokens += int(restart_b.import_prefix_blocks(export).get("tokens", 0))

    async def restart_body(nc, one_chat):
        await asyncio.to_thread(restart_b.warm_chunk_programs, (1,))
        hit0 = restart_b.prefix_cache.hit_tokens
        r = await one_chat(3000, doc(n_prompts - 1), max_new)
        return {
            "warm_imported_tokens": warm_tokens,
            "first_repeat_hit_tokens": restart_b.prefix_cache.hit_tokens - hit0,
            "ttft_ms": round(r["ttft_s"] * 1e3, 1),
        }

    restart = _drive_engine(cfg, params, model_id, tokenizer, restart_b,
                            restart_body)
    if restart["first_repeat_hit_tokens"] <= 0:
        raise RuntimeError(
            "restart with a populated spill tier served its first repeat "
            "prompt with zero prefix hit tokens (warm import broken)"
        )

    on_step = on["round2"]["batcher_phase"].get("batcher_decode_step_p50_ms", 0.0)
    off_step = off["round2"]["batcher_phase"].get("batcher_decode_step_p50_ms", 0.0)
    return {
        "prompts": n_prompts,
        "prompt_tokens_each": prompt_tokens,
        "pool_blocks": pool_blocks,
        "cache_blocks": cache_blocks,
        "working_set_blocks": n_prompts * cache_blocks,
        "tier_on": on,
        "tier_off": off,
        "restart": restart,
        "repeat_ttft_p50_speedup": (
            round(off["round2"]["ttft_p50_ms"] / on["round2"]["ttft_p50_ms"], 2)
            if on["round2"]["ttft_p50_ms"] else 0.0
        ),
        "repeat_hit_tokens_on_vs_off": [on["round2"]["hit_tokens"],
                                        off["round2"]["hit_tokens"]],
        "decode_step_p50_ratio": (
            round(on_step / off_step, 3) if off_step else 0.0
        ),
    }


# ---------------------------------------------------------------------------
# multi-tenant QoS: 3-class overload fairness + preempt vs shed-retry
# ---------------------------------------------------------------------------


def qos_bench(cfg, params, model_id: str = "bench/qos", *,
              slots: int | None = None, n_each: int | None = None,
              max_new: int | None = None) -> dict:
    """Multi-tenant QoS plane (serve/qos.py + batcher admission), driven at
    the batcher seam where the policy lives. Two sub-phases:

    * mix — a 3-class overload (batch/standard/premium tenants, interleaved
      arrival, queue bound far under the offered load) vs a premium-only
      solo baseline of identical geometry. DRR admission must keep premium
      p95 TTFT within ``BENCH_QOS_TTFT_FACTOR`` (default 1.25) of solo,
      with ZERO premium sheds — 100% of the shed lands on batch/standard
      (the depth + fair_share causes).
    * preempt — a premium admit against a full KV pool: with preemption ON
      the batch victim parks on the host tier (resuming bit-identically)
      and premium serves immediately; with slot-suspend OFF the premium
      request takes the kv_pool shed and retries until the pool frees.
      The wall-clock ratio is the cost of shed-retry the preempt path
      removes."""
    import asyncio

    from nats_llm_studio_tpu.engine.generator import SamplingParams
    from nats_llm_studio_tpu.serve.batcher import (
        BatcherOverloaded,
        ContinuousBatcher,
    )
    from nats_llm_studio_tpu.transport.envelope import shed_cause_of

    slots = slots or int(os.environ.get("BENCH_QOS_SLOTS", "2"))
    n_each = n_each or int(os.environ.get("BENCH_QOS_REQS", "6"))
    max_new = max_new or int(os.environ.get("BENCH_QOS_NEW", "8"))
    prompt_len = int(os.environ.get("BENCH_QOS_PROMPT", "48"))
    max_queue = int(os.environ.get("BENCH_QOS_QUEUE", "8"))
    ttft_factor = float(os.environ.get("BENCH_QOS_TTFT_FACTOR", "1.25"))

    def toks(i: int) -> list[int]:
        return [(j * 7 + 3 + i * 13) % 509 for j in range(prompt_len)]

    async def timed_submit(b, prompt, tenant, priority, n_new):
        sp = SamplingParams(temperature=0.0, max_tokens=n_new)
        t0 = time.perf_counter()
        ttft = None
        out = []
        try:
            async for t in b.submit(prompt, sp, tenant=tenant,
                                    priority=priority):
                if ttft is None:
                    ttft = time.perf_counter() - t0
                out.append(t)
        except BatcherOverloaded as e:
            return {"ok": False, "tenant": tenant,
                    "cause": shed_cause_of(str(e)) or "overload"}
        return {"ok": True, "tenant": tenant, "tokens": out,
                "ttft_ms": round((ttft or 0.0) * 1e3, 2),
                "wall_ms": round((time.perf_counter() - t0) * 1e3, 2)}

    def mix_batcher() -> ContinuousBatcher:
        return ContinuousBatcher(
            params, cfg, max_slots=slots, max_seq_len=64 + prompt_len,
            buckets=[64 + prompt_len], max_queue=max_queue,
            admit_coalesce_ms=25.0,
        )

    # -- mix: premium-only solo baseline, then the 3-class overload ----------
    async def run_solo():
        b = mix_batcher()
        try:
            await timed_submit(b, toks(99), "warm", "standard", 2)
            rs = await asyncio.gather(*[
                timed_submit(b, toks(i), "acme", "premium", max_new)
                for i in range(n_each)
            ])
            return sorted(r["ttft_ms"] for r in rs if r["ok"])
        finally:
            b.stop()

    async def run_overload():
        b = mix_batcher()
        try:
            await timed_submit(b, toks(99), "warm", "standard", 2)
            jobs = []
            for i in range(n_each):
                jobs.append(("hobby", "batch", toks(100 + i)))
                jobs.append(("corp", "standard", toks(200 + i)))
                jobs.append(("acme", "premium", toks(i)))
            rs = await asyncio.gather(*[
                timed_submit(b, p, t, c, max_new) for t, c, p in jobs
            ])
            snap = b.tenant_stats.snapshot()
            return rs, snap, dict(b.stats.shed_cause_counts())
        finally:
            b.stop()

    solo_ttfts = asyncio.run(run_solo())
    gc.collect()
    results, tenants, causes = asyncio.run(run_overload())
    gc.collect()
    prem = [r for r in results if r["tenant"] == "acme"]
    prem_ttfts = sorted(r["ttft_ms"] for r in prem if r["ok"])
    shed_by_tenant = {t: row["shed"] for t, row in tenants.items()
                      if row["shed"]}
    if [r for r in prem if not r["ok"]] or shed_by_tenant.get("acme", 0):
        raise RuntimeError(
            f"premium was shed under the 3-class overload: {shed_by_tenant} "
            "(shed must land on batch/standard only)"
        )
    if sum(shed_by_tenant.values()) <= 0:
        raise RuntimeError(
            "overload mix shed nothing — the phase measured no contention "
            f"(causes: {causes})"
        )
    solo_p95 = _pctl(solo_ttfts, 0.95)
    prem_p95 = _pctl(prem_ttfts, 0.95)
    ratio = round(prem_p95 / solo_p95, 3) if solo_p95 else 0.0
    if solo_p95 and ratio > ttft_factor:
        raise RuntimeError(
            f"premium p95 TTFT degraded {ratio}x vs solo under overload "
            f"(bound {ttft_factor}x): solo {solo_p95:.1f} ms, "
            f"mix {prem_p95:.1f} ms"
        )
    mix = {
        "offered_per_class": n_each,
        "solo_ttft_p95_ms": round(solo_p95, 2),
        "premium_ttft_p95_ms": round(prem_p95, 2),
        "premium_ttft_ratio": ratio,
        "premium_served": sum(1 for r in prem if r["ok"]),
        "shed_by_tenant": shed_by_tenant,
        "shed_by_cause": causes,
        "served_by_tenant": {t: row["served"] for t, row in tenants.items()},
    }

    # -- preempt: premium admit on a full pool, preempt ON vs suspend OFF ----
    pre_kw = dict(max_slots=2, max_seq_len=64, buckets=[8, 64],
                  prefill_chunk=32, kv_block_tokens=32, kv_pool_blocks=3,
                  decode_burst=1, admit_coalesce_ms=0.0, paged=True)
    pa = [(j * 7 + 3) % 509 for j in range(33)]
    pb = [(j * 11 + 5) % 509 for j in range(40)]
    na, nb = 12, 8

    async def serve_plain(b, prompt, n_new):
        sp = SamplingParams(temperature=0.0, max_tokens=n_new)
        return [t async for t in b.submit(prompt, sp)]

    ample = ContinuousBatcher(params, cfg, **{**pre_kw, "kv_pool_blocks": 0})
    try:
        want_a = asyncio.run(serve_plain(ample, pa, na))
    finally:
        ample.stop()
    gc.collect()

    async def pressure(b, retry_b: bool):
        """A (batch) decodes first; once 2 tokens arrive, B (premium)
        lands on the exhausted pool. ``retry_b`` = client-side retry loop
        for the shed-mode engine."""
        spa = SamplingParams(temperature=0.0, max_tokens=na)
        spb = SamplingParams(temperature=0.0, max_tokens=nb)
        started = asyncio.get_running_loop().create_future()

        async def run_a():
            t0 = time.perf_counter()
            out = []
            async for t in b.submit(pa, spa, tenant="hobby",
                                    priority="batch"):
                out.append(t)
                if len(out) == 2 and not started.done():
                    started.set_result(None)
            return out, (time.perf_counter() - t0) * 1e3

        async def run_b():
            t0 = time.perf_counter()
            retries = 0
            while True:
                try:
                    out = [t async for t in b.submit(
                        pb, spb, tenant="acme", priority="premium")]
                    return out, (time.perf_counter() - t0) * 1e3, retries
                except BatcherOverloaded:
                    if not retry_b:
                        raise
                    retries += 1
                    await asyncio.sleep(0.025)

        ta = asyncio.ensure_future(run_a())
        await started
        tb = asyncio.ensure_future(run_b())
        (a_toks, a_ms), (b_toks, b_ms, retries) = await asyncio.gather(ta, tb)
        return a_toks, a_ms, b_ms, retries

    b_on = ContinuousBatcher(params, cfg, **{**pre_kw, "qos_preempt": True})
    try:
        a_toks, a_on_ms, b_on_ms, _ = asyncio.run(pressure(b_on, False))
        preempted = b_on.tenant_stats.snapshot().get(
            "hobby", {}).get("preempted", 0)
        on_sheds = dict(b_on.stats.shed_cause_counts())
    finally:
        b_on.stop()
    gc.collect()
    if preempted < 1:
        raise RuntimeError("premium admit on a full pool preempted nothing")
    if on_sheds.get("kv_pool", 0):
        raise RuntimeError(
            f"preempt mode shed {on_sheds['kv_pool']}x on kv_pool — "
            "preempt-to-host-tier is broken"
        )
    if a_toks != want_a:
        raise RuntimeError(
            "preempted batch slot did not resume bit-identically "
            f"({len(a_toks)} vs {len(want_a)} tokens)"
        )

    b_off = ContinuousBatcher(params, cfg, **{**pre_kw, "kv_suspend": False})
    try:
        _, a_off_ms, b_off_ms, retries = asyncio.run(pressure(b_off, True))
        off_sheds = dict(b_off.stats.shed_cause_counts())
    finally:
        b_off.stop()
    gc.collect()
    if off_sheds.get("kv_pool", 0) < 1:
        raise RuntimeError(
            "shed-retry mode never shed on kv_pool — the comparison "
            f"measured nothing (causes: {off_sheds})"
        )

    return {
        "mix": mix,
        "preempt": {
            "victim_resumed_bit_identical": True,
            "victims_preempted": preempted,
            "premium_wall_preempt_ms": round(b_on_ms, 1),
            "premium_wall_shed_retry_ms": round(b_off_ms, 1),
            "shed_retry_attempts": retries,
            "shed_retry_cost_ratio": (
                round(b_off_ms / b_on_ms, 2) if b_on_ms else 0.0
            ),
            "victim_wall_preempt_ms": round(a_on_ms, 1),
            "victim_wall_shed_mode_ms": round(a_off_ms, 1),
            "kv_pool_sheds_shed_mode": off_sheds.get("kv_pool", 0),
        },
    }


# ---------------------------------------------------------------------------
# speculative decoding: prompt-lookup drafts, spec ON vs OFF
# ---------------------------------------------------------------------------


def make_incompressible_prompt(n_tokens: int, seed: int = 3) -> str:
    """~n_tokens of pseudo-random ASCII letters: no repeated n-gram for the
    prompt-lookup index to hit (the adversarial mix for spec decoding)."""
    import random as _random

    r = _random.Random(seed)
    letters = "abcdefghijklmnopqrstuvwxyz "
    return "".join(r.choice(letters) for _ in range(n_tokens))


def spec_decode_bench(cfg, params, model_id: str, *, seq: int | None = None,
                      n_reqs: int | None = None, max_new: int | None = None,
                      spec_k: int | None = None) -> dict:
    """Low-occupancy serving with speculative decoding ON vs OFF
    (serve/spec.py): two prompt mixes — repetition-heavy (greedy; the
    n-gram index hits, drafts accept, decode skips ahead) and
    incompressible (sampled; near-zero hits, measures the overhead floor)
    — each served on spec-on and spec-off engines of identical geometry.
    Reports client-side decode tok/s and TTFT p50 per mode plus the
    drafted/accepted counters scraped off the worker's Prometheus
    exposition (proving the acceptance rate on the wire). Spec-on must
    beat spec-off on the repetition mix at low batch; the incompressible
    mix bounds the regression when drafting never pays."""
    import asyncio

    from nats_llm_studio_tpu.serve.batcher import ContinuousBatcher

    tokenizer = _make_bench_tokenizer(cfg)
    seq = seq or int(os.environ.get("BENCH_SPEC_SEQ", "1024"))
    slots = int(os.environ.get("BENCH_SPEC_SLOTS", "4"))
    n_reqs = n_reqs or int(os.environ.get("BENCH_SPEC_REQS", "8"))
    max_new = max_new or int(os.environ.get("BENCH_SPEC_NEW", "96"))
    spec_k = spec_k or int(os.environ.get("BENCH_SPEC_K", "6"))
    prompt_len = min(max(64, seq // 4), seq - max_new - 2 * (spec_k + 1))

    # repetition-heavy: a looped phrase (the byte-level bench tokenizer
    # turns the repeats into recurring token n-grams) decoded GREEDILY, so
    # generated continuations recur too; incompressible: random letters,
    # sampled at temperature 0.8
    rep_prompt = make_long_prompt(prompt_len)
    inc_prompt = make_incompressible_prompt(prompt_len)
    mixes = [("repetition", rep_prompt, 0.0), ("incompressible", inc_prompt, 0.8)]

    def run_mode(k: int, mix_name: str, prompt: str, temperature: float) -> dict:
        batcher = ContinuousBatcher(
            params, cfg, max_slots=slots, max_seq_len=seq,
            buckets=[b for b in (256, 512) if b < seq] + [seq],
            spec_decode_k=k, spec_max_active=slots,
        )

        async def body(nc, one_chat):
            # warm admit/decode/verify programs outside the timed window —
            # same prompt shape (same prefill bucket) and same generation
            # length (same decode/verify window ladder) as the measured
            # requests, or their compiles land inside the window
            await one_chat(800, f"{prompt} [req 800]", max_new,
                           temperature=temperature)
            if k > 0:
                # a greedy repetition-heavy chat reliably drafts, forcing
                # the verify program to compile here even when THIS mix
                # rarely proposes (the incompressible warm chat may never
                # hit, leaving spec_verify cold)
                await one_chat(801, f"{rep_prompt} [req 801]", max_new,
                               temperature=0.0)
            s0 = batcher.stats.snapshot()
            h0 = _phase_hists(batcher)
            t0 = time.perf_counter()
            sem = asyncio.Semaphore(slots)

            async def one(i: int):
                async with sem:
                    # unique suffix so admits don't collapse into the
                    # prefix cache; the shared body still feeds the n-gram
                    # index
                    return await one_chat(
                        1000 + i, f"{prompt} [req {i:03d}]", max_new,
                        temperature=temperature,
                    )

            reqs = await asyncio.gather(*[one(i) for i in range(n_reqs)])
            wall = time.perf_counter() - t0
            phase = _phase_delta(batcher, s0, h0)
            ttfts = sorted(r["ttft_s"] * 1e3 for r in reqs
                           if r["ttft_s"] == r["ttft_s"])
            decode_tok = sum(max(0, r["completion_tokens"] - 1) for r in reqs)
            decode_s = sum(r["wall_s"] - r["ttft_s"] for r in reqs
                           if r["ttft_s"] == r["ttft_s"])
            out = {
                "requests": n_reqs,
                "completion_tokens": sum(r["completion_tokens"] for r in reqs),
                "ttft_p50_ms": round(_pctl(ttfts, 0.5), 1),
                "decode_tok_s": (
                    round(decode_tok / decode_s, 1) if decode_s > 0 else 0.0
                ),
                "wall_s": round(wall, 2),
                "parse_failures": sum(1 for r in reqs if r["parse_fail"]),
                "batcher_phase": phase,
            }
            s1 = batcher.stats.snapshot()
            out["verifies"] = s1["spec_verifies"] - s0["spec_verifies"]
            drafted = s1["spec_drafted"] - s0["spec_drafted"]
            accepted = s1["spec_accepted"] - s0["spec_accepted"]
            out["drafted"] = drafted
            out["accepted"] = accepted
            if drafted:
                out["accept_rate"] = round(accepted / drafted, 3)
            if k > 0:
                try:  # prove the counters on the wire, not just in-process
                    reply = await nc.request("lmstudio.metrics.prom", b"",
                                             timeout=30.0)
                    for ln in reply.payload.decode().splitlines():
                        if ln.startswith(("lmstudio_spec_drafted_total",
                                          "lmstudio_spec_accepted_total")):
                            out.setdefault("prom_lines", []).append(ln)
                except Exception:  # noqa: BLE001 — exposition is best-effort
                    pass
            return out

        out = _drive_engine(cfg, params, model_id, tokenizer, batcher, body)
        gc.collect()
        return out

    result: dict = {"max_seq_len": seq, "slots": slots, "spec_k": spec_k,
                    "max_new": max_new}
    for mix_name, prompt, temperature in mixes:
        on = run_mode(spec_k, mix_name, prompt, temperature)
        off = run_mode(0, mix_name, prompt, temperature)
        result[mix_name] = {
            "temperature": temperature,
            "spec_on": on,
            "spec_off": off,
            "decode_speedup": (
                round(on["decode_tok_s"] / off["decode_tok_s"], 2)
                if off["decode_tok_s"] else 0.0
            ),
        }
    return result


# ---------------------------------------------------------------------------
# paged KV: one refcounted block pool vs contiguous per-slot rings
# ---------------------------------------------------------------------------


def paged_kv_bench(cfg, params, model_id: str, *, seq: int | None = None,
                   slots: int | None = None, max_new: int | None = None) -> dict:
    """The paged-KV block pool (serve/block_pool.py) against the legacy
    contiguous per-slot rings, at the SAME KV HBM budget:

    * capacity: the legacy engine worst-case-sizes ``slots`` rows of
      ``seq`` tokens each; the paged engine gets a pool of exactly that
      many blocks but 2x the slot count, and the same closed-loop load
      (2x ``slots`` concurrent clients, typical prompts ~seq/8) must run
      them all concurrently — peak_active_slots proves >= 1.5x live slots
      in the same bytes, and the admit-queue p95 delta shows the queueing
      the extra slots absorb (the r05 overload mix hit 6.9 s p95 once its
      96 worst-case rows saturated);
    * sharing: one engine with the prefix cache, a chunk-aligned prompt
      admitted once then resent by 2x ``slots`` concurrent clients — every
      resend must take the FULL-hit zero-copy path (block-table incref,
      no KV copy program at all): the pool gauges prove it
      (blocks_shared > 0 while the sharers decode, cow_copies delta 0,
      full_hits == resends), and the worker's Prometheus exposition is
      scraped so the gauges are proven on the wire."""
    import asyncio

    from nats_llm_studio_tpu.parallel.memory import kv_pool_block_bytes
    from nats_llm_studio_tpu.serve.batcher import ContinuousBatcher

    tokenizer = _make_bench_tokenizer(cfg)
    seq = seq or int(os.environ.get("BENCH_PAGED_SEQ", "1024"))
    slots = slots or int(os.environ.get("BENCH_PAGED_SLOTS", "8"))
    max_new = max_new or int(os.environ.get("BENCH_PAGED_NEW", "32"))
    chunk = int(os.environ.get("BENCH_PAGED_CHUNK",
                               str(max(16, min(256, seq // 4)))))
    rounds = int(os.environ.get("BENCH_PAGED_ROUNDS", "2"))
    # effective block size: the batcher snaps kv_block_tokens down to
    # divide the prefill chunk — mirror it so the budget math is exact
    T = 16
    while T > 1 and chunk % T:
        T //= 2
    # the legacy engine's whole KV budget, expressed in pool blocks: that
    # exact block count IS the paged engine's pool (same bytes, one null
    # block modulo) — any slot-count win is layout, not extra HBM
    budget_blocks = slots * (-(-seq // T))
    budget_bytes = budget_blocks * kv_pool_block_bytes(
        cfg, T, kv_quant=cfg.kv_quant
    )
    content_len = max(16, seq // 8)  # typical prompt << worst-case seq
    workers = 2 * slots
    buckets = [b for b in (64, 256, 512) if b < seq] + [seq]

    def run_capacity(paged: bool) -> dict:
        mode_slots = 2 * slots if paged else slots
        batcher = ContinuousBatcher(
            params, cfg, max_slots=mode_slots, max_seq_len=seq,
            buckets=buckets, prefill_chunk=chunk,
            paged=paged, kv_pool_blocks=budget_blocks if paged else 0,
        )

        async def body(nc, one_chat):
            # warm the singleton + group admit programs and the decode
            # windows the measured load reaches, outside the timed window
            prompt = make_long_prompt(content_len)
            await one_chat(800, f"{prompt} [w]", max_new, temperature=0.0)
            await asyncio.gather(*(
                one_chat(801 + i, f"{prompt} [w{i}]", max_new, temperature=0.0)
                for i in range(min(8, mode_slots))
            ))
            s0 = batcher.stats.snapshot()
            h0 = _phase_hists(batcher)

            async def client(i: int):
                out = []
                for r in range(rounds):
                    out.append(await one_chat(
                        1000 + 16 * (rounds * i + r),
                        f"{prompt} [c {i:02d}.{r}]", max_new, temperature=0.0,
                    ))
                return out

            t0 = time.perf_counter()
            per = await asyncio.gather(*(client(i) for i in range(workers)))
            wall = time.perf_counter() - t0
            phase = _phase_delta(batcher, s0, h0)
            reqs = [r for p in per for r in p]
            ttfts = sorted(r["ttft_s"] * 1e3 for r in reqs
                           if r["ttft_s"] == r["ttft_s"])
            out = {
                "paged": paged,
                "slots": mode_slots,
                "clients": workers,
                "completed": sum(1 for r in reqs if not r["parse_fail"]),
                "parse_failures": sum(1 for r in reqs if r["parse_fail"]),
                "served_tok_s": round(
                    sum(r["completion_tokens"] for r in reqs) / wall, 1
                ),
                "ttft_p50_ms": round(_pctl(ttfts, 0.5), 1),
                "ttft_p95_ms": round(_pctl(ttfts, 0.95), 1),
                "peak_active_slots": batcher.stats.peak_active,
                "wall_s": round(wall, 2),
                "batcher_phase": phase,
            }
            pool = batcher.pool_stats()
            if pool is not None:
                out["pool"] = pool
            return out

        out = _drive_engine(cfg, params, model_id, tokenizer, batcher, body)
        gc.collect()
        return out

    def run_sharing() -> dict:
        n_hits = 2 * slots
        batcher = ContinuousBatcher(
            params, cfg, max_slots=n_hits, max_seq_len=seq,
            buckets=buckets, prefill_chunk=chunk, paged=True,
            prefix_cache_blocks=6 * max(1, chunk // T),
        )

        async def body(nc, one_chat):
            await asyncio.to_thread(batcher.warm_chunk_programs, (1,))
            # measure the template overhead with an UNRELATED probe, then
            # pad the shared prompt to land exactly on a chunk edge: the
            # resend's cached prefix covers ALL n tokens, which is the
            # full-hit (sample-from-cached-logits, zero-prefill) path
            probe = await one_chat(700, "p" * 64, 4)
            overhead = probe["prompt_tokens"] - 64
            base = make_long_prompt(chunk + 23)
            pad = (-(len(base) + overhead)) % batcher.prefill_chunk
            prompt = base + "x" * pad
            miss = await one_chat(701, prompt, max_new, temperature=0.0)
            # one warm resend: the full-hit path's sample-from-cached-logits
            # program compiles here, outside the measured resend wave
            await one_chat(702, prompt, max_new, temperature=0.0)
            p0 = batcher.pool_stats()
            c0 = batcher.prefix_cache.counters()
            shared_peak = 0
            done_evt = asyncio.Event()

            async def poll_shared():
                # blocks_shared is only nonzero WHILE sharers hold refs on
                # the cached blocks (it falls back to cache-only refs when
                # their slots free) — sample it in flight
                nonlocal shared_peak
                while not done_evt.is_set():
                    st = batcher.pool_stats()
                    if st is not None:
                        shared_peak = max(shared_peak, st["blocks_shared"])
                    await asyncio.sleep(0.005)

            poller = asyncio.create_task(poll_shared())
            t0 = time.perf_counter()
            hits = await asyncio.gather(*(
                one_chat(710 + i, prompt, max_new, temperature=0.0)
                for i in range(n_hits)
            ))
            wall = time.perf_counter() - t0
            done_evt.set()
            await poller
            p1 = batcher.pool_stats()
            c1 = batcher.prefix_cache.counters()
            prom_lines: list[str] = []
            try:  # prove the gauges on the wire, not just in-process
                reply = await nc.request("lmstudio.metrics.prom", b"",
                                         timeout=30.0)
                prom_lines = [
                    ln for ln in reply.payload.decode().splitlines()
                    if ln.startswith("lmstudio_kv_pool_")
                ][:12]
            except Exception:  # noqa: BLE001 — exposition is best-effort
                pass
            ttfts = sorted(r["ttft_s"] * 1e3 for r in hits
                           if r["ttft_s"] == r["ttft_s"])
            full_hits = c1["full_hits"] - c0["full_hits"]
            cow = p1["cow_copies"] - p0["cow_copies"]
            return {
                "resends": n_hits,
                "prompt_tokens": miss["prompt_tokens"],
                "parse_failures": sum(1 for r in hits if r["parse_fail"]),
                "full_hits": full_hits,
                "cow_copies": cow,
                "zero_copy": bool(full_hits == n_hits and cow == 0),
                "blocks_shared_peak": shared_peak,
                "miss_ttft_ms": round(miss["ttft_s"] * 1e3, 1)
                if miss["ttft_s"] == miss["ttft_s"] else 0.0,
                "hit_ttft_p50_ms": round(_pctl(ttfts, 0.5), 1),
                "hit_ttft_p95_ms": round(_pctl(ttfts, 0.95), 1),
                "wall_s": round(wall, 2),
                "pool": p1,
                "prom_lines": prom_lines,
            }

        out = _drive_engine(cfg, params, model_id, tokenizer, batcher, body)
        gc.collect()
        return out

    paged_cap = run_capacity(True)
    legacy_cap = run_capacity(False)
    sharing = run_sharing()
    legacy_peak = max(1, legacy_cap.get("peak_active_slots", 1))
    return {
        "max_seq_len": seq,
        "prefill_chunk": chunk,
        "kv_block_tokens": T,
        "kv_budget_blocks": budget_blocks,
        "kv_budget_bytes": budget_bytes,
        "paged": paged_cap,
        "legacy": legacy_cap,
        "slots_ratio": round(
            paged_cap.get("peak_active_slots", 0) / legacy_peak, 2
        ),
        "admit_p95_ms_paged": paged_cap["batcher_phase"][
            "admit_queue_delay_p95_ms"],
        "admit_p95_ms_legacy": legacy_cap["batcher_phase"][
            "admit_queue_delay_p95_ms"],
        "prefix_sharing": sharing,
    }


# ---------------------------------------------------------------------------
# decode kernels: Pallas paged attention vs the XLA gather-view path, and
# grouped-int4 weights vs int8 at equal HBM
# ---------------------------------------------------------------------------


def decode_kernel_bench(cfg, params, *, batches=None, seq=None,
                        max_new=None, quant_batch=None) -> dict:
    """The Pallas paged-decode kernel (ops/paged_attention.py) against the
    XLA gather-view fallback on the SAME paged engine, plus grouped-int4
    weights against int8 at equal HBM:

    * kernel: for each batch width, one paged batcher per forced
      DECODE_KERNEL value serves the same closed greedy wave — decode
      step_ms p50/p95 from the batcher histograms, served tok/s, and the
      engine's first-seen decode-program count (stats.decode_recompiles:
      the Pallas kernel walks the whole table in one program, so it must
      register no more program keys than the XLA window ladder). Greedy tokens must
      MATCH between the kernels — the bit-equivalence the unit tests prove
      per-program, re-proven here at wave scale. Off-TPU the forced Pallas
      path runs in interpreter mode — correct but slow — so the CPU smoke
      keeps the wave tiny and only the TPU step_ms numbers are meaningful
      (``backend`` records which kind this artifact is).
    * quant: fresh leaf-streamed params in int8 and grouped int4 through
      the device-scan decode bench — tok/s, measured weight bytes, and the
      paged-KV slots each mode funds at the int8 run's TOTAL budget
      (weights + quant_batch slots of ``seq``-token block-pool KV): the
      int4 tree's freed HBM must buy at least as many slots as int8.
    """
    import asyncio

    from nats_llm_studio_tpu.engine.generator import SamplingParams
    from nats_llm_studio_tpu.parallel.memory import kv_pool_block_bytes
    from nats_llm_studio_tpu.serve.batcher import ContinuousBatcher

    backend = jax.default_backend()
    batches = batches or [int(x) for x in os.environ.get(
        "BENCH_DK_BATCHES", "32,96").split(",")]
    seq = seq or int(os.environ.get("BENCH_DK_SEQ", "512"))
    max_new = max_new or int(os.environ.get("BENCH_DK_NEW", "48"))
    prompt_len = max(8, seq // 16)
    out: dict = {"backend": backend, "max_seq_len": seq,
                 "decode_new": max_new}

    def run_wave(kernel: str, b: int) -> dict:
        # the knob is read once, at batcher construction — scope the forced
        # value to exactly that window so nothing else inherits it
        prev = os.environ.get("DECODE_KERNEL")
        os.environ["DECODE_KERNEL"] = kernel
        try:
            batcher = ContinuousBatcher(
                params, cfg, max_slots=b, max_seq_len=seq,
                buckets=[x for x in (64, 256) if x < seq] + [seq],
                paged=True,
            )
        finally:
            if prev is None:
                os.environ.pop("DECODE_KERNEL", None)
            else:
                os.environ["DECODE_KERNEL"] = prev
        sp = SamplingParams(temperature=0.0, max_tokens=max_new)
        base = list(range(2, 2 + prompt_len))

        async def one(i: int) -> list[int]:
            return [t async for t in batcher.submit(base + [2 + i % 64], sp)]

        async def wave() -> dict:
            await one(0)  # compile admit + decode programs off the clock
            s0 = batcher.stats.snapshot()
            h0 = _phase_hists(batcher)
            t0 = time.perf_counter()
            toks = await asyncio.gather(*(one(i) for i in range(b)))
            wall = time.perf_counter() - t0
            phase = _phase_delta(batcher, s0, h0)
            return {
                "kernel": batcher.decode_kernel,
                "batch": b,
                "served_tok_s": round(sum(len(t) for t in toks) / wall, 1),
                "wall_s": round(wall, 3),
                "decode_step_p50_ms": phase.get(
                    "batcher_decode_step_p50_ms", 0.0),
                "decode_step_p95_ms": phase.get(
                    "batcher_decode_step_p95_ms", 0.0),
                "decode_recompiles": batcher.stats.snapshot()[
                    "decode_recompiles"],
                "_toks": toks,
            }

        try:
            return asyncio.run(wave())
        finally:
            batcher.stop()
            gc.collect()

    kernels = {}
    for b in batches:
        xla = run_wave("xla", b)
        pal = run_wave("pallas", b)
        match = xla.pop("_toks") == pal.pop("_toks")
        kernels[f"b{b}"] = {
            "xla": xla,
            "pallas": pal,
            "greedy_match": match,
            "step_p50_ratio": round(
                pal["decode_step_p50_ms"] / xla["decode_step_p50_ms"], 3)
            if xla["decode_step_p50_ms"] else None,
        }
    out["kernel"] = kernels
    out["greedy_match_all"] = all(v["greedy_match"] for v in kernels.values())

    if os.environ.get("BENCH_DK_QUANT", "1") != "0":
        qb = quant_batch or int(os.environ.get(
            "BENCH_DK_QB", str(min(batches))))
        T = 16
        slot_bytes = (-(-seq // T)) * kv_pool_block_bytes(
            cfg, T, kv_quant=cfg.kv_quant)
        quant: dict = {}
        for mode in ("int8", "int4"):
            qparams = init_params_int8(cfg, seed=3, mode=mode)
            wbytes = int(sum(x.nbytes for x in jax.tree.leaves(qparams)))
            r = decode_bench(cfg, qparams, qb, prompt_len, seq,
                             max(8, max_new))
            del qparams
            gc.collect()
            quant[mode] = {**r, "weight_bytes": wbytes}
        budget = quant["int8"]["weight_bytes"] + qb * slot_bytes
        for mode in ("int8", "int4"):
            quant[mode]["slots_at_int8_budget"] = int(
                (budget - quant[mode]["weight_bytes"]) // slot_bytes)
        out["quant"] = quant
        out["int4_tok_s_ratio"] = round(
            quant["int4"]["tok_s"] / quant["int8"]["tok_s"], 3)
        out["int4_extra_slots"] = (quant["int4"]["slots_at_int8_budget"]
                                   - quant["int8"]["slots_at_int8_budget"])
    return out


# ---------------------------------------------------------------------------
# tensor-parallel serving: the SAME engine at tp=1 vs tp=N across the mesh
# ---------------------------------------------------------------------------


def tensor_parallel_bench(cfg, params, model_id: str, *, seq: int | None = None,
                          slots: int | None = None, n_reqs: int | None = None,
                          max_new: int | None = None) -> dict:
    """Serving through ``lmstudio.chat_model`` at tp=1 vs tp=N (N = every
    local device, downshifted until the model's head layout divides):
    per-replica served tok/s, batcher decode step_ms p50, and TTFT p50 for
    the same closed wave. tp=N runs ONE replica across N chips — its
    per-replica number is the whole mesh's; ``tok_s_per_chip`` is the
    honest efficiency divisor. Skipped (with a reason) on one device."""
    import asyncio

    from nats_llm_studio_tpu.parallel import build_mesh
    from nats_llm_studio_tpu.parallel.sharding import (
        kv_replicated, shard_params, validate_mesh_for_config,
    )
    from nats_llm_studio_tpu.serve.batcher import ContinuousBatcher

    devices = jax.devices()
    if len(devices) < 2:
        return {"skipped": "single device — no tp axis to bench"}
    tokenizer = _make_bench_tokenizer(cfg)
    seq = seq or int(os.environ.get("BENCH_TP_SEQ", "512"))
    slots = slots or int(os.environ.get("BENCH_TP_SLOTS", "8"))
    n_reqs = n_reqs or int(os.environ.get("BENCH_TP_REQS", "16"))
    max_new = max_new or int(os.environ.get("BENCH_TP_NEW", "64"))

    def servable(tp: int) -> bool:
        try:
            validate_mesh_for_config(
                build_mesh(f"tp={tp}", devices=devices[:tp]), cfg)
            return True
        except ValueError:
            return False

    tp_n = int(os.environ.get("BENCH_TP_N", "0")) or len(devices)
    while tp_n > 1 and not servable(tp_n):
        tp_n //= 2  # e.g. 4 heads on 8 forced host devices -> tp=4
    if tp_n < 2:
        return {"skipped": f"no tp>1 layout divides heads={cfg.n_heads}/"
                           f"{cfg.n_kv_heads} on {len(devices)} devices"}

    def run_mode(tp: int) -> dict:
        mesh = build_mesh(f"tp={tp}", devices=devices[:tp]) if tp > 1 else None
        p = shard_params(params, mesh, cfg) if mesh is not None else params
        batcher = ContinuousBatcher(
            p, cfg, max_slots=slots, max_seq_len=seq,
            buckets=[b for b in (64, 256) if b < seq] + [seq], mesh=mesh,
        )

        async def body(nc, one_chat):
            # warm the singleton admit, the group widths the wave can
            # coalesce into, and the decode windows it sweeps — compiles
            # must not land inside the measured wall
            await one_chat(900, SHORT_PROMPT, 8)
            w = 2
            while w <= min(batcher.max_group_admit, n_reqs, slots):
                await asyncio.gather(
                    *(one_chat(900 + 10 * w + i, SHORT_PROMPT, 8)
                      for i in range(w))
                )
                w *= 2
            await one_chat(990, SHORT_PROMPT, max_new)
            await asyncio.sleep(0.5)  # drain in-flight zombie bursts
            s0 = batcher.stats.snapshot()
            h0 = _phase_hists(batcher)
            t0 = time.perf_counter()
            reqs = await asyncio.gather(
                *(one_chat(1000 + i, f"{SHORT_PROMPT} [{i}]", max_new)
                  for i in range(n_reqs))
            )
            wall = time.perf_counter() - t0
            phase = _phase_delta(batcher, s0, h0)
            ttfts = sorted(r["ttft_s"] * 1e3 for r in reqs
                           if r["ttft_s"] == r["ttft_s"])
            toks = sum(r["completion_tokens"] for r in reqs)
            tok_s = round(toks / wall, 1)
            out = {
                "tp": tp,
                "chips_per_replica": tp,
                "tok_s_per_replica": tok_s,  # one replica serves the wave
                "tok_s_per_chip": round(tok_s / tp, 1),
                "ttft_p50_ms": round(_pctl(ttfts, 0.5), 1),
                "step_ms_p50": phase.get("batcher_decode_step_p50_ms", 0.0),
                "requests": n_reqs,
                "max_tokens": max_new,
                "parse_failures": sum(1 for r in reqs if r["parse_fail"]),
                "batcher_phase": phase,
            }
            if mesh is not None and kv_replicated(mesh, cfg):
                out["kv_replicated"] = True  # GQA fallback path measured
            return out

        out = _drive_engine(cfg, params if mesh is None else p, model_id,
                            tokenizer, batcher, body)
        del p
        gc.collect()
        return out

    on = run_mode(tp_n)
    off = run_mode(1)
    return {
        "devices": len(devices),
        "max_seq_len": seq,
        "slots": slots,
        f"tp{tp_n}": on,
        "tp1": off,
        "per_replica_speedup": (
            round(on["tok_s_per_replica"] / off["tok_s_per_replica"], 2)
            if off.get("tok_s_per_replica") else 0.0
        ),
    }


# ---------------------------------------------------------------------------
# multi-axis serving mesh: dp replicas / routed MoE / sp ring prefill
# ---------------------------------------------------------------------------


def multi_axis_bench(cfg, params, model_id: str, *, seq: int | None = None,
                     slots: int | None = None, n_reqs: int | None = None,
                     max_new: int | None = None) -> dict:
    """The three axes the named mesh adds beyond tp, each measured through
    the LIVE serving path: (a) dp=2 batcher replicas vs one dp=1 replica —
    aggregate tok/s for the same closed wave plus the per-replica request
    split; (b) routed (capacity-factor) vs dense-dispatch MoE — prefill
    wall for a prompt-heavy wave on the same weights; (c) sp=2 ring
    prefill on vs off — long-prompt TTFT. Skipped on one device."""
    import asyncio

    from nats_llm_studio_tpu.parallel import build_mesh, dp_submeshes
    from nats_llm_studio_tpu.parallel.sharding import shard_params
    from nats_llm_studio_tpu.serve.batcher import ContinuousBatcher
    from nats_llm_studio_tpu.serve.dp import DataParallelBatcher

    devices = jax.devices()
    if len(devices) < 2:
        return {"skipped": "single device — no dp/sp axis to bench"}
    tokenizer = _make_bench_tokenizer(cfg)
    seq = seq or int(os.environ.get("BENCH_MA_SEQ", "256"))
    slots = slots or int(os.environ.get("BENCH_MA_SLOTS", "4"))
    n_reqs = n_reqs or int(os.environ.get("BENCH_MA_REQS", "8"))
    max_new = max_new or int(os.environ.get("BENCH_MA_NEW", "16"))
    buckets = [b for b in (64,) if b < seq] + [seq]

    def wave(batcher, prompts, new, replicas=None, wcfg=None, wtok=None,
             mid=None):
        """Closed wave through the broker+worker path: wall, aggregate
        tok/s, TTFT p50 — plus the per-replica request split (wave only,
        warm excluded) when ``replicas`` is given. ``wcfg``/``wtok``/``mid``
        override the engine config for the MoE sub-phase."""
        nrep = len(replicas) if replicas else 1

        async def body(nc, one_chat):
            # compiles must not land inside the wall: warm the singleton
            # admit and the group widths the wave coalesces into. A dp
            # facade spreads a burst least-loaded, so widths are scaled by
            # the replica count (each replica sees a w-wide group) and a
            # second singleton round reaches the sibling replica's grid
            for r_ in range(nrep):
                await one_chat(900 + r_, prompts[0], 4)
            w = 2
            while w <= min(batcher.max_group_admit, len(prompts), slots):
                await asyncio.gather(
                    *(one_chat(910 + w + i, prompts[0], 4)
                      for i in range(w * nrep))
                )
                w *= 2
            await asyncio.sleep(0.3)
            pre = ([r.stats.snapshot().get("requests", 0) for r in replicas]
                   if replicas else None)
            t0 = time.perf_counter()
            reqs = await asyncio.gather(
                *(one_chat(1000 + i, p, new) for i, p in enumerate(prompts))
            )
            wall = time.perf_counter() - t0
            toks = sum(r["completion_tokens"] for r in reqs)
            ttfts = sorted(r["ttft_s"] * 1e3 for r in reqs
                           if r["ttft_s"] == r["ttft_s"])
            res = {
                "wall_s": round(wall, 3),
                "tok_s": round(toks / wall, 1) if wall else 0.0,
                "ttft_p50_ms": round(_pctl(ttfts, 0.5), 1),
                "requests": len(prompts),
            }
            if pre is not None:
                res["replica_requests"] = [
                    r.stats.snapshot().get("requests", 0) - p0
                    for r, p0 in zip(replicas, pre)
                ]
            return res

        return _drive_engine(wcfg or cfg, params, mid or model_id,
                             wtok or tokenizer, batcher, body)

    out: dict = {"devices": len(devices)}
    short = [f"{SHORT_PROMPT} [{i}]" for i in range(n_reqs)]

    # -- (a) dp replicas: aggregate tok/s, dp=2 vs dp=1 ---------------------
    mesh = build_mesh("dp=2", devices=devices[:2])
    reps = [
        ContinuousBatcher(shard_params(params, s, cfg), cfg, max_slots=slots,
                          max_seq_len=seq, buckets=buckets, mesh=s)
        for s in dp_submeshes(mesh)
    ]
    dpb = DataParallelBatcher(reps)
    dpb.start()  # registry._load starts engines eagerly; mirror it so the
    # worker supervisor never reads a not-yet-started replica as crashed
    on = wave(dpb, short, max_new, replicas=reps)
    del dpb, reps
    gc.collect()
    single = ContinuousBatcher(params, cfg, max_slots=slots, max_seq_len=seq,
                               buckets=buckets, mesh=None)
    off = wave(single, short, max_new)
    del single
    gc.collect()
    out["dp"] = {
        "dp2": on, "dp1": off,
        "aggregate_speedup": (round(on["tok_s"] / off["tok_s"], 2)
                              if off.get("tok_s") else 0.0),
    }

    # -- (b) routed vs dense MoE dispatch: prefill-heavy wave ---------------
    moe_kw = dict(n_layers=2, n_experts=8, n_experts_used=2, d_ff=32,
                  max_seq_len=seq, moe_capacity_factor=2.0)
    moe_routed = ModelConfig.tiny(use_routed_moe=True, **moe_kw)
    moe_dense = ModelConfig.tiny(use_routed_moe=False, **moe_kw)
    moe_params = init_params(moe_routed, jax.random.PRNGKey(3))
    # byte tokenizer: 1 char = 1 token, so this is a prefill-dominated wave
    moe_prompts = ["m" * (seq // 2) + str(i) for i in range(4)]

    def moe_wave(mcfg):
        b = ContinuousBatcher(moe_params, mcfg, max_slots=slots,
                              max_seq_len=seq, buckets=buckets, mesh=None)
        r = wave(b, moe_prompts, 2, wcfg=mcfg,
                 wtok=_make_bench_tokenizer(mcfg), mid="bench/moe")
        del b
        gc.collect()
        return r

    routed = moe_wave(moe_routed)
    dense = moe_wave(moe_dense)
    out["moe"] = {
        "routed": routed, "dense": dense,
        "prefill_speedup": (
            round(dense["wall_s"] / routed["wall_s"], 2)
            if routed.get("wall_s") else 0.0
        ),
    }

    # -- (c) sp ring prefill on vs off: long-prompt TTFT --------------------
    long_prompts = ["l" * (seq // 2 + i) for i in range(4)]
    saved_env = os.environ.get("RING_PREFILL_MIN_TOKENS")
    try:
        os.environ["RING_PREFILL_MIN_TOKENS"] = str(seq // 4)
        sp_mesh = build_mesh("sp=2", devices=devices[:2])
        b = ContinuousBatcher(shard_params(params, sp_mesh, cfg), cfg,
                              max_slots=slots, max_seq_len=seq,
                              buckets=buckets, mesh=sp_mesh)
        sp_on = wave(b, long_prompts, 4)
        hists = set(b.stats.program_histograms())
        sp_on["ring_programs"] = sorted(
            n for n in hists if n.endswith("_ring"))
        del b
        gc.collect()
    finally:
        if saved_env is None:
            os.environ.pop("RING_PREFILL_MIN_TOKENS", None)
        else:
            os.environ["RING_PREFILL_MIN_TOKENS"] = saved_env
    b = ContinuousBatcher(params, cfg, max_slots=slots, max_seq_len=seq,
                          buckets=buckets, mesh=None)
    sp_off = wave(b, long_prompts, 4)
    del b
    gc.collect()
    out["sp"] = {
        "sp2_ring": sp_on, "sp_off": sp_off,
        "long_prefill_wall_ratio": (
            round(sp_off["wall_s"] / sp_on["wall_s"], 2)
            if sp_on.get("wall_s") else 0.0
        ),
    }
    return out


# ---------------------------------------------------------------------------
# observability overhead: flight recorder on vs off
# ---------------------------------------------------------------------------


def obs_overhead_bench(cfg, params, *, seq: int | None = None,
                       slots: int | None = None, n_reqs: int | None = None,
                       max_new: int | None = None,
                       rounds: int | None = None) -> dict:
    """Decode throughput with the flight recorder (obs/recorder.py) sampling
    every 25 ms vs recorder disabled, on two batchers of identical geometry.
    Rounds interleave off/on so clock drift and thermal state hit both arms
    equally; medians are compared and reported (``overhead_pct`` beside the
    off arm's own spread, ``noise_floor_pct``). No bound is asserted on them:
    on a shared CPU the spread is larger than the effect, and what the
    recorder and the host spans cost on the chip is measured by the benchmark
    (``--trace 1`` against ``--trace 0``) and written in PERF.md. What is
    asserted is that both arms served every token and the on arm sampled."""
    import asyncio
    import statistics

    from nats_llm_studio_tpu.engine.generator import SamplingParams
    from nats_llm_studio_tpu.obs import FlightRecorder
    from nats_llm_studio_tpu.serve.batcher import ContinuousBatcher

    seq = seq or int(os.environ.get("BENCH_OBS_SEQ", "512"))
    slots = slots or int(os.environ.get("BENCH_OBS_SLOTS", "4"))
    n_reqs = n_reqs or int(os.environ.get("BENCH_OBS_REQS", "8"))
    max_new = max_new or int(os.environ.get("BENCH_OBS_NEW", "64"))
    rounds = rounds or int(os.environ.get("BENCH_OBS_ROUNDS", "3"))
    prompt_len = max(4, min(32, seq // 4))
    buckets = [b for b in (64, 128, 256) if b < seq] + [seq]

    def build(enabled: bool) -> ContinuousBatcher:
        rec = FlightRecorder(enabled=enabled, interval_ms=25.0, dump_dir="")
        return ContinuousBatcher(params, cfg, max_slots=slots,
                                 max_seq_len=seq, buckets=buckets,
                                 recorder=rec)

    served = {}  # batcher -> tokens it streamed, the warm-up round included

    async def round_tok_s(batcher: ContinuousBatcher) -> float:
        sp = SamplingParams(temperature=0.0, max_tokens=max_new)

        async def one(i: int) -> int:
            prompt = [(i * 31 + j) % 97 + 1 for j in range(prompt_len)]
            return len([t async for t in batcher.submit(prompt, sp)])

        t0 = time.perf_counter()
        counts = await asyncio.gather(*[one(i) for i in range(n_reqs)])
        served[batcher] = served.get(batcher, 0) + sum(counts)
        return sum(counts) / (time.perf_counter() - t0)

    async def drive() -> dict:
        b_off, b_on = build(False), build(True)
        try:
            # warm both engines' programs outside the timed rounds
            await round_tok_s(b_off)
            await round_tok_s(b_on)
            off_runs, on_runs = [], []
            for _ in range(rounds):
                off_runs.append(await round_tok_s(b_off))
                on_runs.append(await round_tok_s(b_on))
            frames = b_on.recorder.frames_sampled
        finally:
            b_off.stop()
            b_on.stop()
        off_med = statistics.median(off_runs)
        on_med = statistics.median(on_runs)
        delta_pct = (off_med - on_med) / off_med * 100 if off_med else 0.0
        noise_pct = ((max(off_runs) - min(off_runs)) / off_med * 100
                     if off_med else 0.0)
        return {
            "rounds": rounds, "requests_per_round": n_reqs,
            "max_new": max_new, "recorder_interval_ms": 25.0,
            "off_tok_s": [round(v, 1) for v in off_runs],
            "on_tok_s": [round(v, 1) for v in on_runs],
            "off_median_tok_s": round(off_med, 1),
            "on_median_tok_s": round(on_med, 1),
            "overhead_pct": round(delta_pct, 2),
            "noise_floor_pct": round(noise_pct, 2),
            "frames_sampled": frames,
            "off_tokens_served": served[b_off], "on_tokens_served": served[b_on],
        }

    out = asyncio.run(drive())
    assert out["frames_sampled"] > 0, "recorder-on arm never sampled a frame"
    want = (rounds + 1) * n_reqs * max_new  # the warm-up round and the timed ones
    assert out["off_tokens_served"] == out["on_tokens_served"] == want, out
    gc.collect()
    return out


def efficiency_bench(cfg, params, *, seq: int | None = None,
                     slots: int | None = None, n_reqs: int | None = None,
                     max_new: int | None = None) -> dict:
    """The device-time ledger (obs/roofline.py) under the standard
    overload mix: served requests, client cancels mid-stream, and tight
    deadlines. Asserts that the ledger's category sums reconcile with the
    batcher's measured dispatch wall time to within 10% — every device-ms
    is attributed somewhere. Reports the waste breakdown as a percentage
    of device time, and goodput (served tokens per attributed
    device-second)."""
    import asyncio

    from nats_llm_studio_tpu.engine.generator import SamplingParams
    from nats_llm_studio_tpu.serve.batcher import ContinuousBatcher

    seq = seq or int(os.environ.get("BENCH_EFF_SEQ", "256"))
    slots = slots or int(os.environ.get("BENCH_EFF_SLOTS", "4"))
    n_reqs = n_reqs or int(os.environ.get("BENCH_EFF_REQS", "9"))
    max_new = max_new or int(os.environ.get("BENCH_EFF_NEW", "32"))
    prompt_len = max(4, min(32, seq // 4))
    buckets = [b for b in (64, 128, 256) if b < seq] + [seq]

    batcher = ContinuousBatcher(params, cfg, max_slots=slots,
                                max_seq_len=seq, buckets=buckets)

    async def drive() -> dict:
        sp = SamplingParams(temperature=0.0, max_tokens=max_new)

        def prompt_for(i: int) -> list[int]:
            return [(i * 31 + j) % 97 + 1 for j in range(prompt_len)]

        async def served(i: int) -> int:
            return len([t async for t in batcher.submit(prompt_for(i), sp)])

        async def cancelled(i: int) -> int:
            # client disconnect after 2 tokens: GeneratorExit -> cancel ->
            # the slot's accrued device-ms lands in the cancelled category
            agen = batcher.submit_batched(prompt_for(i), sp)
            got = 0
            async for batch in agen:
                got += len(batch)
                if got >= 2:
                    break
            await agen.aclose()
            return got

        async def tight_deadline(i: int) -> int:
            # a deadline the decode cannot finish inside: either sheds
            # pre-prefill (no device time, no category) or aborts
            # mid-decode (deadline_abort gets the accrued ms) — both are
            # honest outcomes; the reconciliation below must hold either way
            got = 0
            try:
                async for t in batcher.submit(
                    prompt_for(i), sp, deadline=time.monotonic() + 0.25
                ):
                    got += 1
            except Exception:  # noqa: BLE001 — shed/abort envelopes expected
                pass
            return got

        kinds = (served, cancelled, tight_deadline)
        t0 = time.perf_counter()
        results = await asyncio.gather(
            *[kinds[i % len(kinds)](i) for i in range(n_reqs)],
            return_exceptions=True,
        )
        wall_s = time.perf_counter() - t0
        st = batcher.stats
        dt = st.device_time_snapshot()
        return {
            "wall_s": round(wall_s, 3),
            "tokens_served": sum(r for r in results if isinstance(r, int)),
            "device_ms": dt["ms"],
            "device_tokens": dt["tokens"],
            "goodput_tokens_per_device_s": st.goodput_tokens_per_device_s(),
            "dispatch_ms_total": st.dispatch_ms_total,
        }

    try:
        out = asyncio.run(drive())
    finally:
        batcher.stop()

    ledger_ms = sum(out["device_ms"].values())
    busy_ms = out["dispatch_ms_total"]
    assert busy_ms > 0, "no dispatches were timed"
    drift_pct = abs(ledger_ms - busy_ms) / busy_ms * 100
    assert drift_pct <= 10.0, (
        f"device-time ledger ({ledger_ms:.1f} ms) does not reconcile with "
        f"measured dispatch time ({busy_ms:.1f} ms): {drift_pct:.1f}% apart"
    )
    served_ms = out["device_ms"].get("served", 0.0)
    waste_pct = {
        k: round(v / ledger_ms * 100, 2)
        for k, v in sorted(out["device_ms"].items()) if v > 0 and k != "served"
    }
    result = {
        "requests": n_reqs, "max_new": max_new,
        "wall_s": out["wall_s"],
        "tokens_served": out["tokens_served"],
        "device_ms": {k: round(v, 1) for k, v in sorted(out["device_ms"].items()) if v},
        "served_ms_pct": round(served_ms / ledger_ms * 100, 2) if ledger_ms else 0.0,
        "waste_pct": waste_pct,
        "goodput_tokens_per_device_s": round(out["goodput_tokens_per_device_s"], 1),
        "ledger_vs_dispatch_pct": round(drift_pct, 2),
    }
    gc.collect()
    return result


# ---------------------------------------------------------------------------


def byte_level_tokenizer_md(vocab_size: int) -> dict:
    """gpt2-family tokenizer metadata covering all 256 bytes (any text
    encodes, one token per byte), padded with filler tokens to the model's
    vocab; the last id is the eos/control token."""
    from nats_llm_studio_tpu.gguf.constants import TokenType
    from nats_llm_studio_tpu.gguf.tokenizer import _byte_to_unicode

    b2u = _byte_to_unicode()
    tokens = [b2u[b] for b in range(256)]
    while len(tokens) < vocab_size - 1:
        tokens.append(f"<filler_{len(tokens)}>")
    tokens.append("<|eot|>")
    return {
        "tokenizer.ggml.model": "gpt2",
        "tokenizer.ggml.tokens": tokens,
        "tokenizer.ggml.token_type": (
            [int(TokenType.NORMAL)] * (vocab_size - 1)
            + [int(TokenType.CONTROL)]
        ),
        "tokenizer.ggml.merges": [],
        "tokenizer.ggml.eos_token_id": vocab_size - 1,
        "tokenizer.ggml.add_bos_token": False,
    }


def _export_tiny_gguf(models_dir, mid: str, seed: int = 5,
                      max_seq_len: int = 64) -> None:
    """Export a 2-layer tiny model with a byte-level gpt2 tokenizer to
    ``models_dir/mid/m.gguf`` — the resilience phases (chaos, cluster) run
    it so they measure the recovery machinery, not XLA. ``max_seq_len``
    sizes the context (the gateway phase needs prompts past a full prefill
    chunk so the n-fan-out actually shares prefix blocks)."""
    from pathlib import Path

    from nats_llm_studio_tpu.models.export import export_params_to_gguf

    tcfg = ModelConfig.tiny(n_layers=2, max_seq_len=max_seq_len)
    tparams = init_params(tcfg, jax.random.PRNGKey(seed))
    d = Path(models_dir) / mid
    d.mkdir(parents=True)
    export_params_to_gguf(d / "m.gguf", tparams, tcfg, name=mid,
                          tokenizer_md=byte_level_tokenizer_md(tcfg.vocab_size))


def chaos_bench() -> dict:
    """Fault-injected serving (transport/faults.py): a seeded FaultPlan
    severs the client's broker connection mid-run AND crashes the engine
    pump loop once. Every request must still complete — auto-reconnect +
    request retry on the client, supervisor engine restart on the worker.
    Reports recovery behavior (reconnects, restarts, restart latency, total
    wall time), not throughput; runs a tiny model so the phase measures the
    resilience machinery, not XLA."""
    import asyncio
    import tempfile
    from pathlib import Path

    from nats_llm_studio_tpu.config import WorkerConfig
    from nats_llm_studio_tpu.serve import Worker
    from nats_llm_studio_tpu.serve.registry import LocalRegistry
    from nats_llm_studio_tpu.store.manager import ModelStore
    from nats_llm_studio_tpu.transport import EmbeddedBroker, RetryPolicy, connect
    from nats_llm_studio_tpu.transport import faults

    mid = "bench/chaos-tiny"
    n_reqs = int(os.environ.get("BENCH_CHAOS_REQS", "8"))

    async def run(models_dir: Path) -> dict:
        _export_tiny_gguf(models_dir, mid)
        broker = await EmbeddedBroker().start()
        registry = LocalRegistry(
            ModelStore(models_dir), dtype="float32", max_batch_slots=2,
            max_seq_len=64, restart_backoff_s=0.05, restart_backoff_max_s=0.2,
            max_restarts=10, restart_window_s=60.0,
        )
        worker = Worker(
            WorkerConfig(nats_url=broker.url, supervise_interval_s=0.1,
                         engine_heartbeat_timeout_s=0.0),
            registry,
        )
        await worker.start()
        nc = await connect(broker.url, reconnect_wait_s=0.02,
                           reconnect_max_wait_s=0.2)
        body = json.dumps({
            "model": mid,
            "messages": [{"role": "user", "content": "chaos probe"}],
            "max_tokens": 8, "temperature": 0.0, "stream": False,
        }).encode()
        # warm the engine before installing the plan so fault steps land in
        # the measured serving loop, not the initial load
        r = json.loads(
            (await nc.request("lmstudio.chat_model", body, timeout=60)).payload
        )
        assert r.get("ok"), r
        plan = faults.install(
            faults.FaultPlan(seed=int(os.environ.get("BENCH_CHAOS_SEED", "7")))
            .sever(faults.BROKER_PUBLISH, 2, subject="lmstudio.chat_model")
            .raise_at(faults.PUMP, 8, message="bench chaos pump fault")
        )
        retry = RetryPolicy(max_attempts=12, backoff_s=0.2, max_backoff_s=1.0,
                            retry_on_timeout=True)
        t0 = time.perf_counter()
        completed = 0
        try:
            for _ in range(n_reqs):
                r = json.loads(
                    (await nc.request("lmstudio.chat_model", body, timeout=30,
                                      retry=retry)).payload
                )
                if r.get("ok"):
                    completed += 1
            wall_s = time.perf_counter() - t0
        finally:
            faults.clear()
        prom = (
            await nc.request("lmstudio.metrics.prom", b"", timeout=10)
        ).payload.decode()
        restart_ms = {
            line.split()[0].rsplit("_", 1)[-1]: float(line.split()[-1])
            for line in prom.splitlines()
            if line.startswith("lmstudio_engine_restart_ms_")
        }
        out = {
            "requests": n_reqs,
            "completed": completed,
            "faults_fired": plan.fired(),
            "all_faults_fired": plan.done(),
            "client_reconnects": nc.reconnects,
            "last_reconnect_s": round(nc.last_reconnect_s, 4),
            "engine_restarts": registry.engine_restarts_total,
            "inflight_failed_retryable": registry.inflight_failed_retryable
            + sum(
                eng.batcher.stats.inflight_failed_retryable
                for eng in registry.loaded_engines().values()
                if getattr(eng, "batcher", None) is not None
            ),
            "restart_latency_ms": restart_ms,
            "wall_s": round(wall_s, 3),
        }
        await nc.close()
        await worker.drain()
        await broker.stop()
        return out

    with tempfile.TemporaryDirectory() as td:
        return asyncio.run(run(Path(td) / "models"))


def cluster_bench(*, n_workers: int | None = None, n_clients: int | None = None,
                  reqs_per_client: int | None = None,
                  max_new: int | None = None) -> dict:
    """Multi-worker failover (serve/router.py + ISSUE 10 chaos): N workers
    share the queue group on one embedded broker; a worker-scoped sever
    rule (faults.sever_worker) kills one mid-overload-wave, with
    auto-reconnect disabled so the kill is permanent — its queue subs die
    with the connection and the broker routes every later request to the
    survivors. Acceptance: every request is served or fails with a
    *cleanly retryable* envelope — zero client-side timeout expiries — and
    no retry is ever SERVED by a worker named in its own
    X-Excluded-Workers header (the worker self-check bounces those hops;
    the per-worker prom counters in the output are the evidence). Reports
    aggregate tok/s and server-side p95 TTFT (merged per-worker
    lmstudio_ttft_ms histograms) for the cluster wave vs a single-worker
    baseline wave."""
    import asyncio
    import tempfile
    from pathlib import Path

    from nats_llm_studio_tpu.config import WorkerConfig
    from nats_llm_studio_tpu.obs import bucket_pairs, merge
    from nats_llm_studio_tpu.serve import Worker
    from nats_llm_studio_tpu.serve.registry import LocalRegistry
    from nats_llm_studio_tpu.store.manager import ModelStore
    from nats_llm_studio_tpu.transport import EmbeddedBroker, RetryPolicy, connect
    from nats_llm_studio_tpu.transport import faults
    from nats_llm_studio_tpu.transport import protocol as proto
    from nats_llm_studio_tpu.transport.envelope import deadline_header_value

    mid = "bench/cluster-tiny"
    n_workers = n_workers or int(os.environ.get("BENCH_CLUSTER_WORKERS", "2"))
    n_clients = n_clients or int(os.environ.get("BENCH_CLUSTER_CLIENTS", "144"))
    reqs = reqs_per_client or int(os.environ.get("BENCH_CLUSTER_REQS", "1"))
    max_new = max_new or int(os.environ.get("BENCH_CLUSTER_NEW", "8"))
    slots = int(os.environ.get("BENCH_CLUSTER_SLOTS", "4"))
    attempt_s = float(os.environ.get("BENCH_CLUSTER_ATTEMPT_TIMEOUT_S", "8"))
    budget_s = float(os.environ.get("BENCH_CLUSTER_BUDGET_S", "90"))
    kill_step = int(os.environ.get("BENCH_CLUSTER_KILL_STEP",
                                   str(max(4, n_clients // 4))))

    def prom_sum(text: str, family: str) -> float:
        return sum(
            float(line.rsplit(None, 1)[1])
            for line in text.splitlines()
            if line.startswith(family + "{") or line.startswith(family + " ")
        )

    def ttft_p95(prom_texts: list[str]) -> float:
        """p95 from the workers' lmstudio_ttft_ms buckets via the shared
        delta-first merge (nats_llm_studio_tpu.obs.merge — upper bucket
        edge, resolution-honest, no interpolation)."""
        return merge(
            bucket_pairs(t, "lmstudio_ttft_ms") for t in prom_texts
        ).quantile(0.95)

    async def spawn(broker, models_dir: Path, wid: str):
        registry = LocalRegistry(
            ModelStore(models_dir), dtype="float32", max_batch_slots=slots,
            max_seq_len=64, restart_backoff_s=0.05, restart_backoff_max_s=0.2,
            max_restarts=10, restart_window_s=60.0, worker_id=wid,
        )
        worker = Worker(
            WorkerConfig(
                nats_url=broker.url, worker_id=wid,
                cluster_advert_interval_s=0.2,
                supervise_interval_s=0.1, engine_heartbeat_timeout_s=0.0,
                # the kill must be permanent: a severed worker stays dead
                max_reconnects=0,
            ),
            registry,
        )
        await worker.start()
        return worker

    def body_for(tag: str) -> bytes:
        return json.dumps({
            "model": mid,
            "messages": [{"role": "user", "content": f"cluster probe {tag}"}],
            "max_tokens": max_new, "temperature": 0.0, "stream": False,
        }).encode()

    async def wave(nc, tag: str) -> dict:
        out = {"served": 0, "retryable": 0, "hard_failed": 0, "timeouts": 0,
               "tokens": 0}
        lat: list[float] = []
        retry = RetryPolicy(max_attempts=20, backoff_s=0.05, max_backoff_s=0.5,
                            retry_on_timeout=True)

        async def client(i: int) -> None:
            for r_i in range(reqs):
                # explicit wall budget + short per-attempt timeout: an
                # attempt stuck on the killed worker times out quickly and
                # rehops (through the exclusion header) inside the budget
                headers = {proto.DEADLINE_HEADER: deadline_header_value(budget_s)}
                t0 = time.perf_counter()
                try:
                    msg = await nc.request(
                        "lmstudio.chat_model", body_for(f"{tag} c{i} r{r_i}"),
                        timeout=attempt_s, headers=headers, retry=retry,
                    )
                except asyncio.TimeoutError:
                    out["timeouts"] += 1
                    continue
                r = json.loads(msg.payload)
                lat.append(time.perf_counter() - t0)
                if r.get("ok"):
                    out["served"] += 1
                    usage = (r["data"]["response"].get("usage") or {})
                    out["tokens"] += int(usage.get("completion_tokens", 0))
                elif r.get("retryable"):
                    out["retryable"] += 1
                else:
                    out["hard_failed"] += 1

        t0 = time.perf_counter()
        await asyncio.gather(*[client(i) for i in range(n_clients)])
        wall = time.perf_counter() - t0
        out["wall_s"] = round(wall, 3)
        out["tok_s"] = round(out["tokens"] / wall, 1) if wall > 0 else 0.0
        lat.sort()
        out["p95_latency_ms"] = round(1000 * _pctl(lat, 0.95), 1) if lat else 0.0
        return out

    async def scrape(nc, wid: str) -> str:
        msg = await nc.request(f"lmstudio.worker.{wid}.metrics.prom", b"",
                               timeout=10)
        return msg.payload.decode()

    async def run(models_dir: Path) -> dict:
        _export_tiny_gguf(models_dir, mid)

        # -- baseline: the same wave against ONE worker ----------------------
        broker = await EmbeddedBroker().start()
        worker = await spawn(broker, models_dir, "w-base")
        nc = await connect(broker.url, reconnect_wait_s=0.02,
                           reconnect_max_wait_s=0.2)
        warm = json.loads(
            (await nc.request("lmstudio.chat_model", body_for("warm"),
                              timeout=120)).payload
        )
        assert warm.get("ok"), warm
        single = await wave(nc, "single")
        single["ttft_p95_ms"] = ttft_p95([await scrape(nc, "w-base")])
        await nc.close()
        await worker.drain()
        await broker.stop()

        # -- cluster: N workers, one killed mid-wave -------------------------
        broker = await EmbeddedBroker().start()
        wids = [f"w-{i}" for i in range(n_workers)]
        workers = [await spawn(broker, models_dir, wid) for wid in wids]
        nc = await connect(broker.url, reconnect_wait_s=0.02,
                           reconnect_max_wait_s=0.2)
        for wid in wids:
            # warm every engine through its directed subject so fault steps
            # land in the measured wave, not the initial load
            warm = json.loads(
                (await nc.request(f"lmstudio.worker.{wid}.chat_model",
                                  body_for(f"warm {wid}"), timeout=120)).payload
            )
            assert warm.get("ok"), warm
        victim = wids[0]
        plan = faults.install(
            faults.FaultPlan(seed=int(os.environ.get("BENCH_CLUSTER_SEED", "11")))
            .sever_worker(victim, kill_step)
        )
        try:
            cluster = await wave(nc, "cluster")
        finally:
            faults.clear()
        survivors = {}
        prom_texts = []
        for wid in wids[1:]:
            text = await scrape(nc, wid)
            prom_texts.append(text)
            survivors[wid] = {
                "requests_total": prom_sum(text, "lmstudio_requests_total"),
                "excluded_bounce_total": prom_sum(
                    text, "lmstudio_excluded_bounce_total"),
                "drain_bounce_total": prom_sum(
                    text, "lmstudio_drain_bounce_total"),
                "reconnects_total": prom_sum(text, "lmstudio_reconnects_total"),
            }
        cluster["ttft_p95_ms"] = ttft_p95(prom_texts)
        total = n_clients * reqs
        cluster["all_served_or_retryable"] = (
            cluster["timeouts"] == 0 and cluster["hard_failed"] == 0
            and cluster["served"] + cluster["retryable"] == total
        )
        await nc.close()
        for w in workers:
            try:
                await w.drain()
            except (ConnectionError, asyncio.TimeoutError):
                pass  # the victim's connection is (deliberately) dead
        await broker.stop()
        return {
            "workers": n_workers,
            "clients": n_clients,
            "reqs_per_client": reqs,
            "victim": victim,
            "kill_step": kill_step,
            "worker_killed": plan.done(),
            "faults_fired": plan.fired(),
            "single": single,
            "cluster": cluster,
            "survivor_counters": survivors,
            "cluster_vs_single_tok_s": (
                round(cluster["tok_s"] / single["tok_s"], 3)
                if single["tok_s"] else 0.0
            ),
        }

    with tempfile.TemporaryDirectory() as td:
        return asyncio.run(run(Path(td) / "models"))


def disagg_bench(*, n_clients: int | None = None,
                 reqs_per_client: int | None = None,
                 max_new: int | None = None) -> dict:
    """Disaggregated prefill/decode serving (ISSUE 13): the same overload
    wave against (a) a 2-prefill + 2-decode role topology — the role-aware
    ClusterRouter two-hops every chat, so the decode worker pulls the
    prompt's paged-KV blocks from a prefill peer over the kv_export
    subject and decodes from the imported prefix — and (b) 4 monolithic
    workers. Disaggregation's claim is decode-latency STABILITY, not raw
    throughput: with prefill moved off the decode workers, their
    lmstudio_decode_step_ms distribution sits tighter than monolithic
    workers whose decode steps interleave with chunked prefill. Reports
    per-topology served/retryable counts, merged decode-step mean/std/
    variance/p95 (log-histogram bucket midpoints — resolution-honest),
    server-side TTFT p95, and the transfer totals (bytes, ms, failures)
    that prove blocks actually moved rather than every chat silently
    falling back to local prefill."""
    import asyncio
    import tempfile
    from pathlib import Path

    from nats_llm_studio_tpu.config import WorkerConfig
    from nats_llm_studio_tpu.obs import bucket_pairs, merge
    from nats_llm_studio_tpu.serve import Worker
    from nats_llm_studio_tpu.serve.registry import LocalRegistry
    from nats_llm_studio_tpu.serve.router import ClusterRouter
    from nats_llm_studio_tpu.store.manager import ModelStore
    from nats_llm_studio_tpu.transport import EmbeddedBroker, RetryPolicy, connect

    mid = "bench/disagg-tiny"
    n_clients = n_clients or int(os.environ.get("BENCH_DISAGG_CLIENTS", "16"))
    reqs = reqs_per_client or int(os.environ.get("BENCH_DISAGG_REQS", "2"))
    max_new = max_new or int(os.environ.get("BENCH_DISAGG_NEW", "8"))
    slots = int(os.environ.get("BENCH_DISAGG_SLOTS", "4"))
    attempt_s = float(os.environ.get("BENCH_DISAGG_ATTEMPT_TIMEOUT_S", "20"))

    def prom_sum(texts: list[str], family: str, must: str = "") -> float:
        return sum(
            float(line.rsplit(None, 1)[1])
            for text in texts
            for line in text.splitlines()
            if (line.startswith(family + "{") or line.startswith(family + " "))
            and must in line
        )

    def hist_stats(texts: list[str], family: str) -> dict:
        """Mean/variance/p95 across N workers' log-histogram buckets via
        the shared delta-first merge (nats_llm_studio_tpu.obs.merge holds
        the elision and +Inf-collapse rules this bench used to hand-roll)."""
        m = merge(bucket_pairs(t, family) for t in texts)
        if m.count <= 0:
            return {"count": 0, "mean_ms": 0.0, "std_ms": 0.0,
                    "var": 0.0, "p95_ms": 0.0}
        return {"count": int(m.count), "mean_ms": round(m.mean, 3),
                "std_ms": round(m.std, 3), "var": round(m.variance, 4),
                "p95_ms": round(m.quantile(0.95), 3)}

    async def spawn(broker, models_dir: Path, wid: str, role: str):
        registry = LocalRegistry(
            ModelStore(models_dir), dtype="float32", max_batch_slots=slots,
            max_seq_len=64, worker_id=wid,
            # tiny chunks so the short bench prompts cover whole chunks —
            # otherwise nothing is exportable and the phase measures the
            # fallback path instead of the transfer
            prefill_chunk=8, prefix_cache_blocks=64,
        )
        worker = Worker(
            WorkerConfig(
                nats_url=broker.url, worker_id=wid, worker_role=role,
                cluster_advert_interval_s=0.2,
                supervise_interval_s=0.1, engine_heartbeat_timeout_s=0.0,
            ),
            registry,
        )
        await worker.start()
        return worker

    def body_for(tag: str, content: str, tokens: int) -> bytes:
        return json.dumps({
            "model": mid,
            "messages": [{"role": "user", "content": content or tag}],
            "max_tokens": tokens, "temperature": 0.0, "stream": False,
        }).encode()

    async def wave(router, tag: str) -> dict:
        out = {"served": 0, "retryable": 0, "hard_failed": 0, "timeouts": 0,
               "tokens": 0}
        retry = RetryPolicy(max_attempts=8, backoff_s=0.05, max_backoff_s=0.5,
                            retry_on_timeout=True)

        async def client(i: int) -> None:
            for r_i in range(reqs):
                # distinct prompts: every request is a cold prefix on the
                # decode side, so every two-hop really moves blocks
                body = body_for(tag, f"disagg probe {tag} c{i} r{r_i}", max_new)
                try:
                    msg = await router.request_chat(body, timeout=attempt_s,
                                                    retry=retry)
                except (asyncio.TimeoutError, ConnectionError):
                    out["timeouts"] += 1
                    continue
                r = json.loads(msg.payload)
                if r.get("ok"):
                    out["served"] += 1
                    usage = (r["data"]["response"].get("usage") or {})
                    out["tokens"] += int(usage.get("completion_tokens", 0))
                elif r.get("retryable"):
                    out["retryable"] += 1
                else:
                    out["hard_failed"] += 1

        t0 = time.perf_counter()
        await asyncio.gather(*[client(i) for i in range(n_clients)])
        wall = time.perf_counter() - t0
        out["wall_s"] = round(wall, 3)
        out["tok_s"] = round(out["tokens"] / wall, 1) if wall > 0 else 0.0
        return out

    async def run_topology(models_dir: Path, roles: list[str],
                           tag: str) -> dict:
        broker = await EmbeddedBroker().start()
        wids = [f"w-{tag}{i}" for i in range(len(roles))]
        workers = [await spawn(broker, models_dir, wid, role)
                   for wid, role in zip(wids, roles)]
        nc = await connect(broker.url, reconnect_wait_s=0.02,
                           reconnect_max_wait_s=0.2)
        router = await ClusterRouter(nc).start()
        try:
            deadline = time.monotonic() + 10.0
            while (len(router.members()) < len(wids)
                   and time.monotonic() < deadline):
                await asyncio.sleep(0.05)
            for wid in wids:
                # warm every engine through its directed subject: compiles
                # land before the measured wave on both roles
                warm = json.loads(
                    (await nc.request(f"lmstudio.worker.{wid}.chat_model",
                                      body_for(tag, f"warm {wid}", 2),
                                      timeout=120)).payload
                )
                assert warm.get("ok"), warm
            res = await wave(router, tag)
            decode_wids = [w for w, role in zip(wids, roles)
                           if role != "prefill"]
            texts = {wid: (await nc.request(
                f"lmstudio.worker.{wid}.metrics.prom", b"", timeout=10
            )).payload.decode() for wid in wids}
            decode_texts = [texts[w] for w in decode_wids]
            res["decode_step_ms"] = hist_stats(decode_texts,
                                               "lmstudio_decode_step_ms")
            res["ttft_p95_ms"] = hist_stats(decode_texts,
                                            "lmstudio_ttft_ms")["p95_ms"]
            res["two_hop_total"] = router.stats.two_hop_total
            all_texts = list(texts.values())
            res["transfer"] = {
                "import_bytes": prom_sum(
                    all_texts, "lmstudio_kv_transfer_bytes_total",
                    'direction="import"'),
                "export_bytes": prom_sum(
                    all_texts, "lmstudio_kv_transfer_bytes_total",
                    'direction="export"'),
                "import_ms": round(prom_sum(
                    all_texts, "lmstudio_kv_transfer_ms_total",
                    'direction="import"'), 3),
                "failures": prom_sum(
                    all_texts, "lmstudio_kv_transfer_failures_total"),
            }
            return res
        finally:
            await router.stop()
            await nc.close()
            for w in workers:
                try:
                    await w.drain()
                except (ConnectionError, asyncio.TimeoutError):
                    pass
            await broker.stop()

    async def run(models_dir: Path) -> dict:
        _export_tiny_gguf(models_dir, mid)
        disagg = await run_topology(
            models_dir, ["prefill", "prefill", "decode", "decode"], "d")
        mono = await run_topology(models_dir, ["", "", "", ""], "m")
        total = n_clients * reqs
        var_d = disagg["decode_step_ms"]["var"]
        var_m = mono["decode_step_ms"]["var"]
        return {
            "clients": n_clients, "reqs_per_client": reqs, "max_new": max_new,
            "topology": "2 prefill + 2 decode vs 4 monolithic",
            "disagg": disagg,
            "monolithic": mono,
            "all_served_or_retryable": all(
                t["timeouts"] == 0 and t["hard_failed"] == 0
                and t["served"] + t["retryable"] == total
                for t in (disagg, mono)
            ),
            "disagg_lower_decode_variance": (
                var_d < var_m if var_m > 0 else False),
            "decode_variance_ratio": (
                round(var_d / var_m, 6) if var_m > 0 else 0.0),
        }

    with tempfile.TemporaryDirectory() as td:
        return asyncio.run(run(Path(td) / "models"))


def gateway_bench(*, n_reqs: int | None = None,
                  max_new: int | None = None) -> dict:
    """OpenAI HTTP front-door phase (gateway/server.py), three questions:

    (a) what does the HTTP/SSE hop cost? — streaming TTFT p50 through the
        gateway vs the SAME request raw over NATS, same worker, same model;
    (b) what does the fused constrained-decode mask cost per step? — an
        all-True mask forces the masked ext program while changing nothing
        about the distribution, so greedy tokens must stay bit-identical
        and the wall-clock delta IS the mask machinery;
    (c) what do n=4 prompt-sharing choices cost in HBM? — peak live paged-KV
        blocks for n=4 vs n=1 (siblings admit as zero-copy shares of the
        choice-0 prompt blocks, so the ratio lands well under 4x).

    Runs the tiny model so it measures the gateway and batcher machinery,
    not XLA."""
    import asyncio
    import tempfile
    from pathlib import Path

    from nats_llm_studio_tpu.config import WorkerConfig
    from nats_llm_studio_tpu.engine.generator import SamplingParams
    from nats_llm_studio_tpu.gateway import Gateway
    from nats_llm_studio_tpu.serve import Worker
    from nats_llm_studio_tpu.serve.registry import LocalRegistry
    from nats_llm_studio_tpu.store.manager import ModelStore
    from nats_llm_studio_tpu.transport import EmbeddedBroker, connect

    mid = "bench/gw-tiny"
    n_reqs = n_reqs or int(os.environ.get("BENCH_GATEWAY_REQS", "6"))
    max_new = max_new or int(os.environ.get("BENCH_GATEWAY_NEW", "16"))

    class _AllowAll:
        """All-True token mask: routes decode through the masked ext
        program without constraining anything."""

        def __init__(self, vocab):
            self.vocab = vocab
            self.start = 0

        def mask(self, state):
            return np.ones(self.vocab, dtype=bool)

        def advance(self, state, tid):
            return state

        def live(self, state):
            return True

        def accepting(self, state):
            return True

    async def run(models_dir: Path) -> dict:
        # 512-token context: prefill chunks stay at 256, so the fan-out
        # prompt below can span a FULL chunk — prefix-cache harvest (and
        # therefore sibling block sharing) only engages on whole chunks
        _export_tiny_gguf(models_dir, mid, max_seq_len=512)
        broker = await EmbeddedBroker().start()
        registry = LocalRegistry(
            ModelStore(models_dir), dtype="float32",
            max_batch_slots=8, max_seq_len=512,
        )
        worker = Worker(WorkerConfig(nats_url=broker.url), registry)
        await worker.start()
        nc = await connect(broker.url)
        gw = await Gateway(nc, port=0).start()

        stream_req = {
            "model": mid,
            "messages": [{"role": "user", "content": "ttft probe"}],
            "max_tokens": 4, "temperature": 0.0, "stream": True,
        }
        raw_body = json.dumps(stream_req).encode()

        async def raw_ttft() -> float:
            agen = nc.request_stream("lmstudio.chat_model", raw_body,
                                     timeout=60.0)
            t0 = time.perf_counter()
            try:
                async for _ in agen:
                    return time.perf_counter() - t0
            finally:
                await agen.aclose()
            raise RuntimeError("raw stream yielded nothing")

        http_head = (
            f"POST /v1/chat/completions HTTP/1.1\r\nHost: b\r\n"
            f"Content-Length: {len(raw_body)}\r\n\r\n"
        ).encode() + raw_body

        async def gw_ttft() -> float:
            reader, writer = await asyncio.open_connection("127.0.0.1",
                                                           gw.port)
            try:
                t0 = time.perf_counter()
                writer.write(http_head)
                await writer.drain()
                await reader.readuntil(b"\r\n\r\n")   # response head
                await reader.readuntil(b"\n\n")       # first SSE event
                return time.perf_counter() - t0
            finally:
                writer.close()

        # warm both paths (engine load + compiles land here, not in p50)
        await raw_ttft()
        await gw_ttft()
        raw_s = sorted([await raw_ttft() for _ in range(n_reqs)])
        via_s = sorted([await gw_ttft() for _ in range(n_reqs)])
        raw_p50 = _pctl(raw_s, 0.50) * 1e3
        via_p50 = _pctl(via_s, 0.50) * 1e3
        ttft = {
            "raw_nats_p50_ms": round(raw_p50, 2),
            "gateway_p50_ms": round(via_p50, 2),
            "http_hop_delta_ms": round(via_p50 - raw_p50, 2),
            "reqs": n_reqs,
        }

        # (b) constrained-mask per-step overhead on the live batcher
        eng = await registry.get_engine(mid)
        batcher = eng.batcher
        sp = SamplingParams(temperature=0.0, max_tokens=max_new)
        ids = [3, 1, 4, 1, 5]
        dfa = _AllowAll(eng.cfg.vocab_size)

        async def timed(constrain) -> tuple[float, list]:
            t0 = time.perf_counter()
            toks = [t async for t in batcher.submit(ids, sp,
                                                    constrain=constrain)]
            return time.perf_counter() - t0, toks

        await timed(None)       # warm the plain program
        await timed(dfa)        # warm the masked ext program
        plain_s, plain_toks = min([await timed(None) for _ in range(3)],
                                  key=lambda r: r[0])
        ext_s, ext_toks = min([await timed(dfa) for _ in range(3)],
                              key=lambda r: r[0])
        per_plain = plain_s / max(1, len(plain_toks)) * 1e3
        per_ext = ext_s / max(1, len(ext_toks)) * 1e3
        constrained = {
            "plain_ms_per_tok": round(per_plain, 3),
            "masked_ms_per_tok": round(per_ext, 3),
            "overhead_pct": round((per_ext / per_plain - 1.0) * 100, 1)
            if per_plain else 0.0,
            # the bit-identity claim, measured: an all-True mask through the
            # ext program must not change a single greedy token
            "identical_tokens": ext_toks == plain_toks,
        }

        # (c) n=4 vs n=1 peak paged-KV block cost through the n fan-out.
        # The prompt spans a full 256-token prefill chunk so choice 0's
        # prompt blocks land in the prefix cache and the three siblings
        # admit as zero-copy shares of them. Counts are relative to the
        # pre-request pool state (prefix-cache residents stay live).
        async def peak_blocks(n: int, content: str) -> tuple[int, int]:
            payload = {
                "model": mid,
                "messages": [{"role": "user", "content": content}],
                "max_tokens": 10, "temperature": 0.8, "seed": 3, "n": n,
            }
            st0 = batcher.pool_stats()
            task = asyncio.ensure_future(eng.chat(payload))
            peak_live = peak_shared = 0
            while not task.done():
                st = batcher.pool_stats()
                if st is not None:
                    peak_live = max(peak_live,
                                    st["blocks_live"] - st0["blocks_live"])
                    peak_shared = max(peak_shared, st["blocks_shared"])
                await asyncio.sleep(0.002)
            await task
            return peak_live, peak_shared

        fanout: dict = {}
        if batcher.pool_stats() is not None:
            # distinct prompts per arm: no cross-arm prefix-cache hits
            n1_live, _ = await peak_blocks(1, "a" * 300)
            n4_live, n4_shared = await peak_blocks(4, "b" * 300)
            fanout = {
                "n1_peak_blocks_live": n1_live,
                "n4_peak_blocks_live": n4_live,
                "n4_peak_blocks_shared": n4_shared,
                "blocks_ratio": round(n4_live / n1_live, 2) if n1_live else 0.0,
                "cow_copies": batcher.pool_stats()["cow_copies"],
            }
        else:
            fanout = {"skipped": "paged KV off (KV_PAGED=0)"}

        out = {
            "ttft": ttft,
            "constrained_mask": constrained,
            "n_fanout": fanout,
            "gateway_requests_total": gw.requests_total,
            "gateway_streams_total": gw.streams_total,
        }
        await gw.stop()
        await nc.close()
        await worker.drain()
        await broker.stop()
        return out

    with tempfile.TemporaryDirectory() as td:
        return asyncio.run(run(Path(td) / "models"))


def obs_cluster_bench(*, n_reqs: int | None = None,
                      max_new: int | None = None) -> dict:
    """Cluster observability plane (ISSUE 14): a 1-prefill + 1-decode role
    topology served through the steered ClusterRouter with the fleet
    Aggregator attached. Exercises the plane end to end and reports what
    it claims: (a) the aggregator's cluster-merged TTFT p95 must agree
    with this bench's own delta-first merge over the SAME scrape — they
    share nats_llm_studio_tpu.obs.merge, so the phase asserts equality,
    not closeness; (b) a served two-hop chat queried back through
    ``lmstudio.debug.trace.<trace_id>`` must come back as ONE assembled
    tree whose stages cover the steering attempt, the decode serve, the
    decode-side KV pull, and the prefill-side KV export."""
    import asyncio
    import tempfile
    from pathlib import Path

    from nats_llm_studio_tpu.config import WorkerConfig
    from nats_llm_studio_tpu.obs import Aggregator, bucket_pairs, merge
    from nats_llm_studio_tpu.serve import Worker
    from nats_llm_studio_tpu.serve.registry import LocalRegistry
    from nats_llm_studio_tpu.serve.router import ClusterRouter
    from nats_llm_studio_tpu.store.manager import ModelStore
    from nats_llm_studio_tpu.transport import EmbeddedBroker, RetryPolicy, connect

    mid = "bench/obs-cluster-tiny"
    n_reqs = n_reqs or int(os.environ.get("BENCH_OBS_CLUSTER_REQS", "4"))
    max_new = max_new or int(os.environ.get("BENCH_OBS_CLUSTER_NEW", "8"))

    async def spawn(broker, models_dir: Path, wid: str, role: str):
        registry = LocalRegistry(
            ModelStore(models_dir), dtype="float32", max_batch_slots=2,
            max_seq_len=64, worker_id=wid,
            # whole tiny prompts must cover full chunks or nothing is
            # exportable and the trace never grows its kv hops
            prefill_chunk=8, prefix_cache_blocks=32,
        )
        worker = Worker(
            WorkerConfig(
                nats_url=broker.url, worker_id=wid, worker_role=role,
                cluster_advert_interval_s=0.2,
                supervise_interval_s=0.1, engine_heartbeat_timeout_s=0.0,
            ),
            registry,
        )
        await worker.start()
        return worker

    async def run(models_dir: Path) -> dict:
        _export_tiny_gguf(models_dir, mid)
        broker = await EmbeddedBroker().start()
        roles = {"w-obs-p": "prefill", "w-obs-d": "decode"}
        workers = [await spawn(broker, models_dir, wid, role)
                   for wid, role in roles.items()]
        nc = await connect(broker.url, reconnect_wait_s=0.02,
                           reconnect_max_wait_s=0.2)
        router = await ClusterRouter(nc).start()
        agg = Aggregator(nc, scrape_interval_s=0.2)
        # no scrape loop: the phase drives scrape_once() itself so the
        # p95-parity comparison runs against one known scrape
        await agg.start(scrape_loop=False)
        try:
            deadline = time.monotonic() + 10.0
            while ((len(router.members()) < len(roles)
                    or len(agg.live_workers()) < len(roles))
                   and time.monotonic() < deadline):
                await asyncio.sleep(0.05)
            retry = RetryPolicy(max_attempts=6, backoff_s=0.05,
                                max_backoff_s=0.5, retry_on_timeout=True)
            served, trace_ids = 0, []
            for i in range(n_reqs):
                body = json.dumps({
                    "model": mid,
                    "messages": [{"role": "user",
                                  "content": f"obs cluster probe {i}"}],
                    "max_tokens": max_new, "temperature": 0.0, "stream": False,
                }).encode()
                msg = await router.request_chat(body, timeout=60.0, retry=retry)
                r = json.loads(msg.payload)
                if r.get("ok"):
                    served += 1
                    tid = (r["data"]["response"].get("stats") or {}).get(
                        "trace", {}).get("trace_id")
                    if tid:
                        trace_ids.append(tid)

            # span batches are fire-and-forget: give the last flush a beat,
            # then poll until the newest trace shows its kv hops
            tree: dict = {}
            if trace_ids:
                q = f"lmstudio.debug.trace.{trace_ids[-1]}"
                deadline = time.monotonic() + 5.0
                while time.monotonic() < deadline:
                    resp = json.loads(
                        (await nc.request(q, b"", timeout=5)).payload)
                    tree = resp.get("data") or {}
                    if tree.get("span_count", 0) >= 4:
                        break
                    await asyncio.sleep(0.1)
            stages: set[str] = set()

            def walk(nodes: list) -> None:
                for n in nodes:
                    if n.get("stage"):
                        stages.add(n["stage"])
                    walk(n.get("children") or [])

            walk(tree.get("roots") or [])

            texts = await agg.scrape_once()
            bench_p95 = merge(
                bucket_pairs(t, "lmstudio_ttft_ms") for t in texts.values()
            ).quantile(0.95)
            agg_p95 = next(
                (float(line.rsplit(None, 1)[1])
                 for line in agg.render_cluster().splitlines()
                 if line.startswith("lmstudio_cluster_ttft_p95_ms")), -1.0)
            return {
                "served": served,
                "scraped_workers": len(texts),
                "agg_ttft_p95_ms": agg_p95,
                "merge_ttft_p95_ms": round(bench_p95, 3),
                "p95_match": agg_p95 == round(bench_p95, 3),
                "trace_span_count": tree.get("span_count", 0),
                "trace_stages": sorted(stages),
                "two_hop_trace": {"router.attempt", "worker.serve",
                                  "worker.kv_pull",
                                  "worker.kv_export"} <= stages,
                "spans_ingested": agg.spans.spans_total,
                "slo_alerts": agg.alerts_total,
            }
        finally:
            await agg.stop()
            await router.stop()
            await nc.close()
            for w in workers:
                try:
                    await w.drain()
                except (ConnectionError, asyncio.TimeoutError):
                    pass
            await broker.stop()

    with tempfile.TemporaryDirectory() as td:
        return asyncio.run(run(Path(td) / "models"))


def autoscale_bench(*, n_clients: int | None = None,
                    reqs_per_client: int | None = None,
                    max_new: int | None = None) -> dict:
    """Elastic autoscaling (ISSUE 15): the seconds-cold-start claims and
    the kill-and-replace loop, end to end on one embedded broker.

    (a) time-to-first-served-token COLD vs PRECOMPILED: the first worker
        loads the tiny model against an empty persistent XLA compile
        cache and pays the compiles; the second spawn (fresh registry,
        fresh batcher, same cache dir) re-jits the grid from the cache —
        exactly the artifact pull-time precompile (registry.pull) writes
        at pull_model time, so the delta IS the cold-start saving the
        precompile hook buys. Per-stage cache hit/miss deltas are the
        evidence the second load actually hit.
    (b) kill-and-replace wall time: an :class:`Autoscaler` with
        min_workers=2 watches the advert stream; severing one worker's
        connection mid-wave must trigger a below_min spawn, and the
        replacement's first advert triggers a warm prefix-cache handoff
        from the survivor — re-serving the survivor-primed prompt at the
        replacement must land prefix-cache hits (hit tokens reported).
    (c) the ramp wave's aggregate tok/s, every request served or cleanly
        retryable (zero client-side timeout expiries)."""
    import asyncio
    import tempfile
    from pathlib import Path

    from nats_llm_studio_tpu.config import WorkerConfig
    from nats_llm_studio_tpu.obs import (
        compile_cache_counts,
        install_compile_cache_listener,
    )
    from nats_llm_studio_tpu.serve import Autoscaler, Worker
    from nats_llm_studio_tpu.serve.registry import LocalRegistry
    from nats_llm_studio_tpu.store.manager import ModelStore
    from nats_llm_studio_tpu.transport import EmbeddedBroker, RetryPolicy, connect
    from nats_llm_studio_tpu.transport import protocol as proto
    from nats_llm_studio_tpu.transport.envelope import deadline_header_value

    mid = "bench/autoscale-tiny"
    n_clients = n_clients or int(os.environ.get("BENCH_AUTOSCALE_CLIENTS", "8"))
    reqs = reqs_per_client or int(os.environ.get("BENCH_AUTOSCALE_REQS", "2"))
    max_new = max_new or int(os.environ.get("BENCH_AUTOSCALE_NEW", "8"))
    attempt_s = float(os.environ.get("BENCH_AUTOSCALE_ATTEMPT_TIMEOUT_S", "8"))
    budget_s = float(os.environ.get("BENCH_AUTOSCALE_BUDGET_S", "90"))
    replace_wait_s = float(os.environ.get("BENCH_AUTOSCALE_REPLACE_WAIT_S", "60"))

    # the cold-vs-precompiled comparison needs an EMPTY persistent compile
    # cache under the first worker. Where JAX_COMPILATION_CACHE_DIR places
    # the cache it is used as it stands (the hit/miss deltas below say how
    # cold "cold" was); otherwise the phase gets its own sub-directory of
    # the program's fixed cache path, emptied first
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import shutil

        from jax.experimental.compilation_cache import compilation_cache as _cc
        from nats_llm_studio_tpu.config import DEFAULT_COMPILE_CACHE_DIR

        phase_cache = os.path.join(DEFAULT_COMPILE_CACHE_DIR, "bench_autoscale")
        shutil.rmtree(phase_cache, ignore_errors=True)
        jax.config.update("jax_compilation_cache_dir", phase_cache)
        # jax latches its cache at the process's first compile (earlier
        # phases have long since compiled): re-init so the switch takes
        _cc.reset_cache()
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    install_compile_cache_listener()

    def make_worker(broker, models_dir: Path, wid: str) -> Worker:
        registry = LocalRegistry(
            ModelStore(models_dir), dtype="float32", max_batch_slots=4,
            max_seq_len=128, prefill_chunk=8, prefix_cache_blocks=32,
            restart_backoff_s=0.05, restart_backoff_max_s=0.2,
            max_restarts=10, restart_window_s=60.0, worker_id=wid,
        )
        return Worker(
            WorkerConfig(nats_url=broker.url, worker_id=wid,
                         cluster_advert_interval_s=0.1,
                         supervise_interval_s=0.1,
                         engine_heartbeat_timeout_s=0.0,
                         kv_transfer_timeout_s=120.0),
            registry,
        )

    def body_for(content: str) -> bytes:
        return json.dumps({
            "model": mid,
            "messages": [{"role": "user", "content": content}],
            "max_tokens": max_new, "temperature": 0.0, "stream": False,
        }).encode()

    async def run(models_dir: Path) -> dict:
        # 128-token context: the chat template alone costs ~20 tokens, so
        # the warm-handoff probe needs headroom past the 64-token default
        _export_tiny_gguf(models_dir, mid, seed=13, max_seq_len=128)
        broker = await EmbeddedBroker().start()
        nc = await connect(broker.url, reconnect_wait_s=0.02,
                           reconnect_max_wait_s=0.2)

        # stamp autoscale events off the bus as they land — replace wall
        # time is kill -> spawn_live, measured the way an operator would
        event_marks: dict[str, float] = {}
        spawned_ids: list[str] = []

        async def on_event(msg) -> None:
            try:
                ev = json.loads(msg.payload)
            except ValueError:
                return
            if ev.get("kind") != "autoscale":
                return
            event_marks.setdefault(ev.get("action", ""), time.perf_counter())
            if ev.get("action") == "spawn" and ev.get("worker_id"):
                spawned_ids.append(ev["worker_id"])

        ev_sub = await nc.subscribe("lmstudio.events", cb=on_event)

        # primes the donor's prefix cache AND is re-served at the
        # replacement after handoff — long enough to fill whole prefill
        # chunks (the cache only harvests full blocks)
        warm_probe = "warm handoff probe: the survivor primes this prefix"

        # -- (a) cold vs precompiled time-to-first-served-token --------------
        cc0 = compile_cache_counts()
        t0 = time.perf_counter()
        victim = make_worker(broker, models_dir, "w-cold")
        await victim.start()
        r = json.loads((await nc.request(
            "lmstudio.worker.w-cold.chat_model", body_for(warm_probe),
            timeout=120)).payload)
        assert r.get("ok"), r
        ttfs_cold = time.perf_counter() - t0
        cc1 = compile_cache_counts()

        t0 = time.perf_counter()
        survivor = make_worker(broker, models_dir, "w-pre")
        await survivor.start()
        r = json.loads((await nc.request(
            "lmstudio.worker.w-pre.chat_model", body_for(warm_probe),
            timeout=120)).payload)
        assert r.get("ok"), r
        ttfs_pre = time.perf_counter() - t0
        cc2 = compile_cache_counts()

        # -- (b) kill-and-replace under the autoscaler -----------------------
        spawned: dict[str, Worker] = {}

        async def spawn_fn(wid: str):
            w = make_worker(broker, models_dir, wid)
            await w.start()
            spawned[wid] = w
            return w

        a = Autoscaler(
            nc, nats_url=broker.url, min_workers=2, max_workers=3,
            interval_s=0.25, stale_after_s=1.0, spawn_grace_s=60.0,
            cooldown_s=1.0, up_dwell_s=0.5, down_dwell_s=1e9,
            handoff_prefixes=4, spawn_fn=spawn_fn,
        )
        # subscribe first, tick only once both live workers have adverted:
        # the loop must start in steady state, not spawn its way out of an
        # empty membership view
        await a.start(control_loop=False)
        for _ in range(200):
            if len(a._members) >= 2:
                break
            await asyncio.sleep(0.05)
        assert len(a._members) >= 2, a._members
        a._task = asyncio.ensure_future(a._loop())

        kill_at = time.perf_counter()
        await victim.nc.close()  # permanent: its queue subs die with it

        wave = {"served": 0, "retryable": 0, "hard_failed": 0,
                "timeouts": 0, "tokens": 0}
        retry = RetryPolicy(max_attempts=40, backoff_s=0.05, max_backoff_s=0.5,
                            retry_on_timeout=True)

        async def client(i: int) -> None:
            for r_i in range(reqs):
                # explicit wall budget + short per-attempt timeout: an
                # attempt stuck on the killed worker times out quickly and
                # rehops inside the budget
                headers = {proto.DEADLINE_HEADER: deadline_header_value(budget_s)}
                try:
                    msg = await nc.request(
                        "lmstudio.chat_model",
                        body_for(f"ramp probe c{i} r{r_i}"),
                        timeout=attempt_s, headers=headers, retry=retry,
                    )
                except asyncio.TimeoutError:
                    wave["timeouts"] += 1
                    continue
                resp = json.loads(msg.payload)
                if resp.get("ok"):
                    wave["served"] += 1
                    usage = (resp["data"]["response"].get("usage") or {})
                    wave["tokens"] += int(usage.get("completion_tokens", 0))
                elif resp.get("retryable"):
                    wave["retryable"] += 1
                else:
                    wave["hard_failed"] += 1

        t0 = time.perf_counter()
        await asyncio.gather(*[client(i) for i in range(n_clients)])
        wave_wall = time.perf_counter() - t0
        wave["wall_s"] = round(wave_wall, 3)
        wave["tok_s"] = (round(wave["tokens"] / wave_wall, 1)
                         if wave_wall > 0 else 0.0)
        total = n_clients * reqs
        all_ok = (wave["timeouts"] == 0 and wave["hard_failed"] == 0
                  and wave["served"] + wave["retryable"] == total)

        # the replacement's first advert triggers the warm handoff from the
        # survivor; wait (bounded) for the blocks to land before re-serving
        # the primed prompt at it
        deadline = time.monotonic() + replace_wait_s
        repl_wid = None
        repl = None
        while time.monotonic() < deadline:
            repl_wid = spawned_ids[0] if spawned_ids else None
            repl = spawned.get(repl_wid) if repl_wid else None
            if repl is not None and repl._warm_handoff_received >= 1:
                break
            await asyncio.sleep(0.1)

        warm_hits: dict = {}
        ttfs_replacement = -1.0
        replacement_error = ""
        if repl is not None:
            r = json.loads((await nc.request(
                f"lmstudio.worker.{repl_wid}.chat_model",
                body_for(warm_probe), timeout=120,
                retry=RetryPolicy(max_attempts=6, backoff_s=0.2,
                                  max_backoff_s=1.0, retry_on_timeout=True),
            )).payload)
            if r.get("ok"):
                # upper bound: the replacement may have served wave traffic
                # earlier; this stamps kill -> primed-prompt served
                ttfs_replacement = time.perf_counter() - kill_at
            else:
                replacement_error = str(r.get("error", ""))
            eng = repl.registry.loaded_engines().get(mid)
            if eng is not None and getattr(eng, "batcher", None) is not None:
                warm_hits = dict(eng.batcher.prefix_cache.counters())

        autoscale_prom = a.render_prometheus()
        out = {
            "clients": n_clients,
            "reqs_per_client": reqs,
            "ttfs_cold_s": round(ttfs_cold, 3),
            "ttfs_precompiled_s": round(ttfs_pre, 3),
            "compile_cache_dir": jax.config.jax_compilation_cache_dir,
            "cold_compile_cache": {
                "misses": cc1["misses"] - cc0["misses"],
                "hits": cc1["hits"] - cc0["hits"],
            },
            "precompiled_compile_cache": {
                "misses": cc2["misses"] - cc1["misses"],
                "hits": cc2["hits"] - cc1["hits"],
            },
            "wave": wave,
            "all_served_or_retryable": all_ok,
            "replace_wall_s": (
                round(event_marks["spawn_live"] - kill_at, 3)
                if "spawn_live" in event_marks else -1.0
            ),
            "ttfs_replacement_s": round(ttfs_replacement, 3),
            "replacement": repl_wid or "",
            "replacement_error": replacement_error,
            "warm_handoff_received": (
                repl._warm_handoff_received if repl is not None else 0),
            "survivor_handoff_sent": survivor._warm_handoff_sent,
            "warm_prefix_hits": int(warm_hits.get("hits", 0)),
            "warm_prefix_hit_tokens": int(warm_hits.get("hit_tokens", 0)),
            "spawns_total": a.spawns_total,
            "drains_total": a.drains_total,
            "spawn_failures_total": a.spawn_failures_total,
            "breaker_open": a.breaker_open(),
            "autoscale_prom_families": sum(
                1 for line in autoscale_prom.splitlines()
                if line.startswith("# TYPE lmstudio_autoscale_")
            ),
        }
        await a.stop()
        try:
            await ev_sub.unsubscribe()
        except (ConnectionError, ValueError):
            pass
        await nc.close()
        for w in [victim, survivor, *spawned.values()]:
            try:
                await w.drain()
            except (ConnectionError, asyncio.TimeoutError):
                pass  # the victim's connection is (deliberately) dead
        await broker.stop()
        return out

    with tempfile.TemporaryDirectory() as td:
        return asyncio.run(run(Path(td) / "models"))


FINAL_LINE_BUDGET = 1500  # harness line-buffer bound on the final JSON line


def _summarize_detail(detail: dict) -> dict:
    """Per-phase summary for the final line: top-level scalars verbatim,
    phase dicts reduced to their scalar members — sweeps, histograms, and
    nested sub-phases live in the BENCH_LOCAL_*.json sibling instead."""
    out: dict = {}
    for k, v in detail.items():
        if isinstance(v, dict):
            s = {kk: vv for kk, vv in v.items()
                 if vv is None or isinstance(vv, (str, int, float, bool))}
            if s:
                out[k] = s
        elif v is None or isinstance(v, (str, int, float, bool)):
            out[k] = v
    return out


def _print_final(obj: dict) -> None:
    """Emit the results object as ONE compact JSON line, guaranteed LAST on
    stdout: flush both streams first so buffered warmup chatter cannot land
    after (or interleave with) the line a harness machine-parses.

    The line is capped at FINAL_LINE_BUDGET chars: past that, the full
    ``detail`` moves to a sibling BENCH_LOCAL_<timestamp>.json (path
    reported as ``detail_file``) and the line carries a scalar per-phase
    summary, largest entries dropped first until it fits."""
    from pathlib import Path

    line = json.dumps(obj, separators=(",", ":"))
    if len(line) > FINAL_LINE_BUDGET:
        obj = dict(obj)
        full = obj.get("detail") or {}
        path = Path(__file__).with_name(
            time.strftime("BENCH_LOCAL_%Y%m%d_%H%M%S.json"))
        try:
            path.write_text(json.dumps(full, indent=2, sort_keys=True))
            obj["detail_file"] = str(path)
        except OSError as e:  # read-only checkout: keep the summary anyway
            obj["detail_file_error"] = f"{type(e).__name__}: {e}"
        summary = _summarize_detail(full)
        obj["detail"] = summary
        line = json.dumps(obj, separators=(",", ":"))
        while len(line) > FINAL_LINE_BUDGET and summary:
            # shrink inside the biggest phase before dropping any phase
            # outright: CI smoke asserts phase *presence* on this line, so
            # a phase key must survive even if its fields don't
            biggest = max(summary, key=lambda k: len(json.dumps({k: summary[k]})))
            entry = summary[biggest]
            if isinstance(entry, dict) and entry:
                fattest = max(entry, key=lambda k: len(json.dumps({k: entry[k]})))
                entry.pop(fattest)
            else:
                # scalar or already-empty dict: popping the key is the only
                # shrink left (unreachable in practice — a full set of empty
                # phase dicts is far under budget)
                summary.pop(biggest)
            line = json.dumps(obj, separators=(",", ":"))
    # the artifact contract: whatever shrinking happened above, the line a
    # harness machine-parses MUST fit its line buffer — blowing this is a
    # bench bug (a phase emitting unbounded scalars), not a soft condition
    assert len(line) <= FINAL_LINE_BUDGET, (
        f"final line {len(line)} chars > {FINAL_LINE_BUDGET} after shrink"
    )
    sys.stderr.flush()
    sys.stdout.flush()
    print(line, flush=True)


def _run_phase(detail: dict, name: str, fn) -> None:
    """Run one bench phase, once: ``detail[name]`` on success,
    ``detail[f"{name}_error"]`` on failure. The later phases still run —
    one artifact shows everything that is broken — and ``main`` exits
    non-zero if any phase recorded an error."""
    try:
        detail[name] = fn()
    except Exception as e:  # noqa: BLE001 — recorded, and fails the run at exit
        detail[f"{name}_error"] = f"{type(e).__name__}: {e}"
        gc.collect()


def _exit_code(detail: dict) -> int:
    failed = sorted(k for k in detail if k.endswith("_error"))
    if failed:
        print(f"bench: {len(failed)} phase(s) failed: {failed}", file=sys.stderr)
    return 1 if failed else 0


def main() -> int:
    """Run the bench; the exit code is non-zero if any phase failed."""
    from nats_llm_studio_tpu.config import WorkerConfig

    # the persistent compile cache, placed by the program's one rule
    WorkerConfig().configure_jax()
    tiny = bool(os.environ.get("BENCH_TINY"))
    dev = jax.devices()[0]
    detail: dict = {"quant": "int8", "platform": dev.platform,
                    "device_kind": dev.device_kind,
                    "device_count": len(jax.devices())}

    if tiny:
        # smoke path: an UNQUANTIZED tiny model — named honestly so nobody
        # mistakes a smoke line for an 8B int8 measurement
        cfg = ModelConfig.tiny()
        from nats_llm_studio_tpu.models.llama import ensure_lm_head

        params = ensure_lm_head(init_params(cfg, jax.random.PRNGKey(0)))
        r = decode_bench(cfg, params, batch=2, prompt_len=16, seq_len=64, steps=8)
        tiny_detail = {"quant": cfg.dtype, "platform": detail["platform"],
                       "tiny": r}
        if os.environ.get("BENCH_SPEC", "1") != "0":
            # micro-run of the spec phase (CI smoke coverage)
            _run_phase(tiny_detail, "spec_decode", lambda: spec_decode_bench(
                cfg, params, "bench/tiny",
                seq=256, n_reqs=2, max_new=24, spec_k=4,
            ))
        if os.environ.get("BENCH_PAGED", "1") != "0":
            # micro-run of the paged-KV phase: equal-budget capacity ratio
            # + zero-copy full-prefix sharing at tiny scale (CI smoke)
            _run_phase(tiny_detail, "paged_kv", lambda: paged_kv_bench(
                cfg, params, "bench/tiny", seq=256, slots=2, max_new=12,
            ))
        if os.environ.get("BENCH_KV_TIER", "1") != "0":
            # micro-run of the KV-tiering phase: 10 documents against a
            # 1-document prefix budget — demote on round 1, promote on
            # round 2, restart-with-warm-cache, zero kv_pool sheds
            _run_phase(tiny_detail, "kv_tiering", lambda: kv_tiering_bench(
                cfg, params, "bench/tiny",
                seq=256, chunk=64, slots=2, n_prompts=10, max_new=8,
            ))
        if os.environ.get("BENCH_QOS", "1") != "0":
            # micro-run of the multi-tenant QoS phase: 3-class overload
            # fairness (premium TTFT held, shed confined to batch/standard)
            # + preempt-to-host-tier vs shed-retry on a full pool
            _run_phase(tiny_detail, "qos", lambda: qos_bench(
                cfg, params, "bench/tiny", slots=2, n_each=4, max_new=8,
            ))
        if os.environ.get("BENCH_DECODE_KERNEL", "1") != "0":
            # micro-run of the decode-kernel phase: forced Pallas runs in
            # interpreter mode on CPU, so the smoke proves greedy parity
            # and the recompile-count ordering, not step latency
            _run_phase(tiny_detail, "decode_kernel",
                       lambda: decode_kernel_bench(
                           cfg, params, batches=[2], seq=128, max_new=8,
                           quant_batch=2,
                       ))
        if os.environ.get("BENCH_TP", "1") != "0":
            # micro-run of the tensor-parallel phase: meaningful under
            # forced host devices (XLA_FLAGS=--xla_force_host_platform_
            # device_count=8), reports skipped on one device
            _run_phase(tiny_detail, "tensor_parallel",
                       lambda: tensor_parallel_bench(
                           cfg, params, "bench/tiny",
                           seq=128, slots=4, n_reqs=4, max_new=16,
                       ))
        if os.environ.get("BENCH_MULTI_AXIS", "1") != "0":
            # micro-run of the multi-axis mesh phase: dp=2 replica aggregate
            # vs dp=1, routed-vs-dense MoE prefill, sp ring on/off — only
            # meaningful under forced host devices, skips on one device
            _run_phase(tiny_detail, "multi_axis", lambda: multi_axis_bench(
                cfg, params, "bench/tiny",
                seq=128, slots=2, n_reqs=4, max_new=8,
            ))
        if os.environ.get("BENCH_OBS", "1") != "0":
            # micro-run of the recorder-overhead phase: on CPU smoke the
            # noise-floor guard does the work; TPU runs get the real 1% bound
            _run_phase(tiny_detail, "obs_overhead", lambda: obs_overhead_bench(
                cfg, params, seq=128, slots=2, n_reqs=2, max_new=12, rounds=2,
            ))
        if os.environ.get("BENCH_EFFICIENCY", "1") != "0":
            # micro-run of the efficiency phase: device-time ledger
            # reconciliation under the served/cancel/deadline mix (CI smoke
            # asserts the phase lands in the detail)
            _run_phase(tiny_detail, "efficiency", lambda: efficiency_bench(
                cfg, params, seq=128, slots=2, n_reqs=6, max_new=16,
            ))
        if os.environ.get("BENCH_CHAOS", "1") != "0":
            # fault-injected serving: recovery must hold in CI smoke too
            _run_phase(tiny_detail, "chaos", chaos_bench)
        if os.environ.get("BENCH_CLUSTER", "1") != "0":
            # micro-run of the multi-worker failover phase: two workers,
            # one killed mid-wave — every request served or cleanly
            # retryable (CI smoke asserts the flag on the final line)
            _run_phase(tiny_detail, "cluster", lambda: cluster_bench(
                n_workers=2, n_clients=12, reqs_per_client=2, max_new=8,
            ))
        if os.environ.get("BENCH_DISAGG", "1") != "0":
            # micro-run of the disaggregated prefill/decode phase: 2+2 role
            # topology vs 4 monolithic under a small overload wave — CI
            # smoke asserts the phase lands in the detail
            _run_phase(tiny_detail, "disagg", lambda: disagg_bench(
                n_clients=8, reqs_per_client=2, max_new=8,
            ))
        if os.environ.get("BENCH_GATEWAY", "1") != "0":
            # micro-run of the HTTP front-door phase: gateway-vs-raw TTFT,
            # all-True-mask per-step overhead (tokens must stay identical),
            # and the n=4 prompt-sharing block cost (CI smoke)
            _run_phase(tiny_detail, "gateway", lambda: gateway_bench(
                n_reqs=4, max_new=12,
            ))
        if os.environ.get("BENCH_OBS_CLUSTER", "1") != "0":
            # micro-run of the cluster observability phase: assembled
            # two-hop trace + aggregator-vs-bench TTFT p95 parity (CI
            # smoke asserts the phase lands in the detail)
            _run_phase(tiny_detail, "obs_cluster", lambda: obs_cluster_bench(
                n_reqs=3, max_new=8,
            ))
        if os.environ.get("BENCH_AUTOSCALE", "1") != "0":
            # micro-run of the elastic autoscaling phase: cold vs
            # precompiled spawn TTFS, kill-and-replace with warm prefix
            # handoff (CI smoke asserts the phase lands in the detail)
            _run_phase(tiny_detail, "autoscale", lambda: autoscale_bench(
                n_clients=6, reqs_per_client=2, max_new=8,
            ))
        rc = _exit_code(tiny_detail)  # before the final line shrinks detail
        _print_final({
            "metric": "tiny_smoke_decode_tok_s",
            "value": r["tok_s"], "unit": "tok/s/chip",
            "vs_baseline": 0.0,
            "detail": tiny_detail,
        })
        return rc

    if jax.default_backend() != "tpu":
        # a device metric comes from the device: no CPU number is ever
        # printed under the headline's name
        sys.exit(
            f"bench: the headline path measures a TPU; JAX initialised "
            f"{jax.default_backend()!r}. BENCH_TINY=1 is the CPU smoke."
        )

    # -- headline: Llama-3-8B int8, batch sweep -----------------------------
    # flash prefill on the real chip (the serving stack's configuration;
    # decode's T=1 path is unaffected by the flag); decode_unroll makes
    # every per-layer cache access a static view (1440 -> 1799 tok/s at
    # b32); int8 KV (ops/kvcache.py) halves cache traffic AND capacity,
    # moving the batch frontier from b48 to b96 — measured b48 2608,
    # b64 3436, b96 4391 tok/s. BENCH_KV=none reverts to the bf16 cache.
    kv = os.environ.get("BENCH_KV", "int8")
    cfg = LLAMA3_8B.with_(use_flash_attention=True, decode_unroll=True,
                          kv_quant=kv)
    detail["kv_quant"] = kv
    params = init_params_int8(cfg)
    # defaults scale with the kv mode: the bf16 cache's HBM frontier is b48
    # (b56+ trips the 15.75 GB AOT compile budget next to the 8.7 GB int8
    # params — the estimate double-counts the donated cache); int8 KV halves
    # the cache and moves it to b96
    # b80 rides below the b96 HBM-pressure edge (b96 swings ~15% run to run
    # as the allocator sits ~0.5 GB from the ceiling); best-of reports it
    # when b96 lands on a bad run
    default_batches = "8,16,32,48,64,80,96" if kv == "int8" else "8,16,32,48"
    batches = [int(b) for b in
               os.environ.get("BENCH_BATCHES", default_batches).split(",")]
    prompt_len = int(os.environ.get("BENCH_PROMPT", "128"))
    # seq 512 (not 1024): the b32 [B, L, Hkv, S, D] cache at 1024 puts the
    # compile-time HBM estimate 0.4 GB over the 15.75 GB budget next to the
    # 8.7 GB int8 params (the AOT path double-counts the donated cache);
    # decode reads are window-bounded, so seq only sizes the allocation
    seq_len = int(os.environ.get("BENCH_SEQ", "512"))
    steps = int(os.environ.get("BENCH_STEPS", "128"))
    sweep = {}
    for b in batches:
        sweep[f"b{b}"] = decode_bench(cfg, params, b, prompt_len, seq_len, steps)
    # steady-state guard (VERDICT r3 weak #2): flag any point whose
    # prefill_s is >2x every neighbor's — a stall that slipped past
    # best-of-2 timing stays visible in the artifact instead of being
    # silently published as steady state
    keys = [f"b{b}" for b in batches]
    for i, kname in enumerate(keys):
        neigh = [sweep[keys[j]]["prefill_s"] for j in (i - 1, i + 1)
                 if 0 <= j < len(keys)]
        if neigh and sweep[kname]["prefill_s"] > 2 * max(neigh):
            sweep[kname]["prefill_outlier"] = True
    best_b = max(sweep, key=lambda k: sweep[k]["tok_s"])
    tok_s = sweep[best_b]["tok_s"]
    detail["llama3_8b"] = {"sweep": sweep, "best": best_b,
                           "prompt_len": prompt_len, "decode_steps": steps}

    # every phase below goes through _run_phase: one attempt, a failure is
    # recorded under <name>_error and fails the run's exit code

    # -- long-context prefill (16k, single flash dispatch) ------------------
    if os.environ.get("BENCH_LONG", "1") != "0":
        _run_phase(detail, "long_prefill", lambda: long_prefill_bench(
            cfg, params, int(os.environ.get("BENCH_LONG_T", "16384"))
        ))

    # -- end-to-end over NATS with the SAME 8B engine ------------------------
    if os.environ.get("BENCH_E2E", "1") != "0":
        _run_phase(detail, "e2e", lambda: e2e_nats_bench(
            cfg, params, "bench/llama3-8b",
            clients_b=96 if kv == "int8" else 48,
        ))
        gc.collect()

    # -- long-context SERVING: >=4k-token prompts via chat_model -------------
    if os.environ.get("BENCH_E2E_LONG", "1") != "0":
        _run_phase(detail, "e2e_long", lambda: e2e_long_context_bench(
            cfg, params, "bench/llama3-8b"
        ))
        gc.collect()

    # -- prefix cache: shared-system-prompt serving, ON vs OFF ---------------
    if os.environ.get("BENCH_PREFIX", "1") != "0":
        _run_phase(detail, "prefix_cache", lambda: prefix_cache_bench(
            cfg, params, "bench/llama3-8b"
        ))
        gc.collect()

    # -- speculative decoding: prompt-lookup drafts, ON vs OFF ---------------
    if os.environ.get("BENCH_SPEC", "1") != "0":
        _run_phase(detail, "spec_decode", lambda: spec_decode_bench(
            cfg, params, "bench/llama3-8b"
        ))
        gc.collect()

    # -- paged KV: block pool vs contiguous rings at equal HBM ---------------
    if os.environ.get("BENCH_PAGED", "1") != "0":
        _run_phase(detail, "paged_kv", lambda: paged_kv_bench(
            cfg, params, "bench/llama3-8b"
        ))
        gc.collect()

    # -- KV tiering: swap-don't-shed at 10x the prefix budget, ON vs OFF ----
    if os.environ.get("BENCH_KV_TIER", "1") != "0":
        _run_phase(detail, "kv_tiering", lambda: kv_tiering_bench(
            cfg, params, "bench/llama3-8b"
        ))
        gc.collect()

    # -- multi-tenant QoS: 3-class fairness + preempt vs shed-retry ----------
    if os.environ.get("BENCH_QOS", "1") != "0":
        _run_phase(detail, "qos", lambda: qos_bench(
            cfg, params, "bench/llama3-8b"
        ))
        gc.collect()

    # -- decode kernels: Pallas vs XLA step latency, int4 vs int8 ------------
    if os.environ.get("BENCH_DECODE_KERNEL", "1") != "0":
        _run_phase(detail, "decode_kernel", lambda: decode_kernel_bench(
            cfg, params
        ))
        gc.collect()

    # -- tensor-parallel serving: tp=1 vs tp=N on the same engine ------------
    if os.environ.get("BENCH_TP", "1") != "0":
        _run_phase(detail, "tensor_parallel", lambda: tensor_parallel_bench(
            cfg, params, "bench/llama3-8b"
        ))
        gc.collect()

    # -- multi-axis mesh: dp replicas / routed MoE / sp ring prefill ---------
    if os.environ.get("BENCH_MULTI_AXIS", "1") != "0":
        _run_phase(detail, "multi_axis", lambda: multi_axis_bench(
            cfg, params, "bench/llama3-8b"
        ))
        gc.collect()

    # -- observability overhead: flight recorder on vs off -------------------
    if os.environ.get("BENCH_OBS", "1") != "0":
        _run_phase(detail, "obs_overhead", lambda: obs_overhead_bench(
            cfg, params
        ))
        gc.collect()

    # -- efficiency: device-time ledger + waste attribution ------------------
    if os.environ.get("BENCH_EFFICIENCY", "1") != "0":
        _run_phase(detail, "efficiency", lambda: efficiency_bench(
            cfg, params
        ))
        gc.collect()

    # -- chaos: fault-injected serving recovery (own tiny model) -------------
    if os.environ.get("BENCH_CHAOS", "1") != "0":
        _run_phase(detail, "chaos", chaos_bench)
        gc.collect()

    # -- cluster: kill-a-worker failover under overload (own tiny model) -----
    if os.environ.get("BENCH_CLUSTER", "1") != "0":
        _run_phase(detail, "cluster", cluster_bench)
        gc.collect()

    # -- disagg: 2+2 prefill/decode roles vs 4 monolithic (own tiny model) ---
    if os.environ.get("BENCH_DISAGG", "1") != "0":
        _run_phase(detail, "disagg", disagg_bench)
        gc.collect()

    # -- gateway: HTTP hop TTFT, constrained-mask cost, n fan-out HBM --------
    if os.environ.get("BENCH_GATEWAY", "1") != "0":
        _run_phase(detail, "gateway", gateway_bench)
        gc.collect()

    # -- obs_cluster: assembled two-hop trace + aggregator p95 parity --------
    if os.environ.get("BENCH_OBS_CLUSTER", "1") != "0":
        _run_phase(detail, "obs_cluster", obs_cluster_bench)
        gc.collect()

    # -- autoscale: cold/precompiled/warm-handoff TTFS, kill-and-replace -----
    if os.environ.get("BENCH_AUTOSCALE", "1") != "0":
        _run_phase(detail, "autoscale", autoscale_bench)
        gc.collect()

    del params
    gc.collect()

    # -- config-1 parity: granite-2b ----------------------------------------
    if os.environ.get("BENCH_GRANITE", "1") != "0":
        def _granite_phase() -> dict:
            gcfg = GRANITE_2B.with_(
                use_flash_attention=jax.default_backend() == "tpu",
                decode_unroll=True,
            )
            gparams = init_params_int8(gcfg, seed=1)
            try:
                return decode_bench(gcfg, gparams, 32, prompt_len, 1024, steps)
            finally:
                del gparams
                gc.collect()

        _run_phase(detail, "granite2b", _granite_phase)

    # -- MoE on-chip number (BASELINE config 4): routed vs dense dispatch ---
    if os.environ.get("BENCH_MOE", "1") != "0":
        _run_phase(detail, "moe", lambda: moe_bench(
            batch=int(os.environ.get("BENCH_MOE_BATCH", "32")),
            prompt_len=prompt_len, steps=steps,
        ))

    rc = _exit_code(detail)  # before the final line shrinks detail
    _print_final({
        "metric": f"llama3_8b_int8_decode_tok_s.{best_b}",
        "value": tok_s,
        "unit": "tok/s/chip",
        "vs_baseline": round(tok_s / NORTH_STAR_TOK_S, 3),
        "detail": detail,
    })
    return rc


if __name__ == "__main__":
    sys.exit(main())
