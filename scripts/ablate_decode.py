"""Ablation timings for the decode step on the real chip.

Methodology: every variant here runs as a 64-iteration ``lax.scan`` inside
ONE jit call, so per-step numbers are device time with the host dispatch
amortized over the scan.

Variants:
  full      - real forward + sample_rows        (the serving decode step)
  greedy    - real forward + argmax only        (isolates the sampler)
  window    - forward with attn_window=128      (isolates KV-cache reads)
  matmuls   - layer matmuls only, no attention  (weight streaming floor)
  attn      - cache write + attention only      (cache bandwidth)
  sampler   - sample_rows on fixed logits       (sampler alone)

Run:  python scripts/ablate_decode.py [batch] [quant]   (quant: none|int8)
"""

from __future__ import annotations

import sys
import time
from functools import partial

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
import numpy as np

from bench import GRANITE_2B
from nats_llm_studio_tpu.engine.sampling import sample_rows
from nats_llm_studio_tpu.models.llama import ensure_lm_head, forward, init_params, make_cache
from nats_llm_studio_tpu.ops.layers import gqa_attention_hmajor, rms_norm, swiglu
from nats_llm_studio_tpu.ops.wquant import mm, quantizable, quantize_weight

STEPS = 64


def _sync(out) -> None:
    np.asarray(jax.tree.leaves(out)[0].ravel()[0])


def scan_bench(name, step, carry, args=(), n_outer=5, extra=""):
    """step: (args, carry) -> carry. Times STEPS iterations inside one jit.
    ``args`` (e.g. params) passes through jit arguments so weights are real
    HBM operands, not baked-in constants."""

    @jax.jit
    def run(args, carry):
        return jax.lax.scan(
            lambda c, _: (step(args, c), None), carry, None, length=STEPS
        )[0]

    out = run(args, carry)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(n_outer):
        out = run(args, out)
    _sync(out)
    dt = (time.perf_counter() - t0) / (n_outer * STEPS)
    print(f"{name:8s}: {dt*1e3:7.3f} ms/step {extra}", flush=True)
    return dt


def main() -> None:
    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    quant = sys.argv[2] if len(sys.argv) > 2 else "int8"
    seq = 1024
    cfg = GRANITE_2B
    params = ensure_lm_head(init_params(cfg, jax.random.PRNGKey(0)))
    if quant == "int8":
        params = {
            k: (quantize_weight(v, device=True) if quantizable(k) and k == "lm_head"
                else v)
            for k, v in params.items()
        }
        params["blocks"] = {
            k: (quantize_weight(v, device=True) if quantizable(k) else v)
            for k, v in params["blocks"].items()
        }
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"batch={batch} quant={quant} params={nbytes/1e9:.2f} GB", flush=True)

    K, V = make_cache(cfg, batch, seq)
    kv_bytes = K.nbytes + V.nbytes
    fwd = partial(forward, cfg=cfg)
    temp = jnp.full((batch,), 0.8, jnp.float32)
    topk = jnp.zeros((batch,), jnp.int32)
    topp = jnp.ones((batch,), jnp.float32)
    seeds = jnp.arange(batch, dtype=jnp.int32)

    # full: forward + sampler (pos advances each step like real decode)
    def full_step(params, c):
        tok, K, V, pos = c
        logits, K, V = fwd(params, tokens=tok[:, None], k_cache=K, v_cache=V, start_pos=pos)
        nxt = sample_rows(logits[:, -1, :], seeds, pos, temp, topk, topp)
        return (nxt, K, V, pos + 1)

    c0 = (jnp.ones((batch,), jnp.int32), K, V, jnp.full((batch,), 128, jnp.int32))
    dt = scan_bench("full", full_step, c0, args=params)
    print(f"          = {batch/dt:7.1f} tok/s", flush=True)

    def greedy_step(params, c):
        tok, K, V, pos = c
        logits, K, V = fwd(params, tokens=tok[:, None], k_cache=K, v_cache=V, start_pos=pos)
        return (jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32), K, V, pos + 1)

    K, V = make_cache(cfg, batch, seq)
    scan_bench("greedy", greedy_step, (jnp.ones((batch,), jnp.int32), K, V,
                                       jnp.full((batch,), 128, jnp.int32)), args=params)

    def window_step(params, c):
        tok, K, V, pos = c
        logits, K, V = fwd(params, tokens=tok[:, None], k_cache=K, v_cache=V,
                           start_pos=pos, attn_window=256)
        return (jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32), K, V, pos + 1)

    K, V = make_cache(cfg, batch, seq)
    scan_bench("window", window_step, (jnp.ones((batch,), jnp.int32), K, V,
                                       jnp.full((batch,), 128, jnp.int32)), args=params)

    # noattn: full forward structure — cache write + scan threading of the
    # caches as xs/ys — but the attention read replaced by a q passthrough.
    # (full - noattn) = attention read; (noattn - matmuls) = cache threading.
    hq_, hkv_, d_ = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def noattn_step(params, c):
        tok, K, V, pos = c
        x = params["embed"][tok[:, None]].astype(jnp.dtype(cfg.dtype)) * cfg.embedding_scale
        zero = jnp.zeros((), jnp.int32)

        def block(carry, inputs):
            x, K, V = carry
            p, l = inputs
            h = rms_norm(x, p["attn_norm"], cfg.rms_eps)
            q = mm(h, p["wq"]).reshape(batch, 1, hq_, d_)
            k = mm(h, p["wk"]).reshape(batch, 1, hkv_, d_)
            v = mm(h, p["wv"]).reshape(batch, 1, hkv_, d_)

            def write_row(cache_b, rows_b, s):  # cache_b [L,H,S,D]
                return jax.lax.dynamic_update_slice(
                    cache_b, rows_b[None].astype(cache_b.dtype), (l, zero, s, zero)
                )

            K = jax.vmap(write_row)(K, k.transpose(0, 2, 1, 3), pos)
            V = jax.vmap(write_row)(V, v.transpose(0, 2, 1, 3), pos)
            x = x + mm(q.reshape(batch, 1, hq_ * d_), p["wo"]) * cfg.residual_scale
            h = rms_norm(x, p["ffn_norm"], cfg.rms_eps)
            x = x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"]) * cfg.residual_scale
            return (x, K, V), None

        (x, K, V), _ = jax.lax.scan(
            block, (x, K, V),
            (params["blocks"], jnp.arange(cfg.n_layers, dtype=jnp.int32)),
        )
        logits = mm(rms_norm(x, params["out_norm"], cfg.rms_eps), params["lm_head"])
        return (jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32), K, V, pos + 1)

    K, V = make_cache(cfg, batch, seq)
    scan_bench("noattn", noattn_step, (jnp.ones((batch,), jnp.int32), K, V,
                                       jnp.full((batch,), 128, jnp.int32)), args=params)

    # matmuls only (same weights incl lm_head, no attention/cache/embed)
    x0 = jnp.ones((batch, 1, cfg.d_model), jnp.dtype(cfg.dtype))

    def matmul_step(params, x):
        def block(x, p):
            h = rms_norm(x, p["attn_norm"], cfg.rms_eps)
            q = mm(h, p["wq"])
            k = mm(h, p["wk"])
            v = mm(h, p["wv"])
            o = jnp.concatenate([q, k, v], -1)[..., : cfg.n_heads * cfg.head_dim]
            x = x + mm(o, p["wo"]) * cfg.residual_scale
            h = rms_norm(x, p["ffn_norm"], cfg.rms_eps)
            x = x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"]) * cfg.residual_scale
            return x, None

        x, _ = jax.lax.scan(block, x, params["blocks"])
        logits = mm(rms_norm(x, params["out_norm"], cfg.rms_eps), params["lm_head"])
        return x * 0.999 + jnp.sum(logits, dtype=x.dtype) * 1e-12

    scan_bench("matmuls", matmul_step, x0, args=params)

    # attention only: cache write + gqa read over the carried full cache
    # (layout [B, L, Hkv, S, D], same carry structure as models.llama.forward)
    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def attn_step(_, c):
        acc, K, V, pos = c
        q = jnp.ones((batch, 1, hq, d), K.dtype) * acc.astype(K.dtype)
        k1 = jnp.ones((batch, hkv, 1, d), K.dtype)
        key_pos = jnp.arange(seq, dtype=jnp.int32)
        mask = key_pos[None, None, :] <= pos[:, None, None]
        zero = jnp.zeros((), jnp.int32)

        def block(carry, l):
            acc, K, V = carry

            def write_row(cache_b, rows_b, s):  # cache_b [L,H,S,D]
                return jax.lax.dynamic_update_slice(cache_b, rows_b[None], (l, zero, s, zero))

            K = jax.vmap(write_row)(K, k1, pos)
            V = jax.vmap(write_row)(V, k1, pos)
            kc = jax.lax.dynamic_slice(
                K, (zero, l, zero, zero, zero), (batch, 1, hkv, seq, d))[:, 0]
            vc = jax.lax.dynamic_slice(
                V, (zero, l, zero, zero, zero), (batch, 1, hkv, seq, d))[:, 0]
            out = gqa_attention_hmajor(q, kc, vc, mask, cfg.attn_scale)
            return (acc + jnp.sum(out, dtype=jnp.float32), K, V), None

        (acc2, K, V), _ = jax.lax.scan(
            block, (jnp.zeros((), jnp.float32), K, V),
            jnp.arange(cfg.n_layers, dtype=jnp.int32))
        return (acc2 * 1e-9, K, V, pos + 1)

    K, V = make_cache(cfg, batch, seq)
    dt = scan_bench("attn", attn_step,
                    (jnp.zeros((), jnp.float32), K, V, jnp.full((batch,), 128, jnp.int32)),
                    extra=f"(cache {kv_bytes/1e9:.2f} GB)")
    print(f"          = {kv_bytes/dt/1e9:7.1f} GB/s cache read", flush=True)

    logits0 = jax.random.normal(jax.random.PRNGKey(1), (batch, cfg.vocab_size), jnp.float32)

    def sampler_step(_, c):
        logits, i = c
        nxt = sample_rows(logits, seeds, i, temp, topk, topp)
        return (logits + nxt[:, None] * 1e-9, i + 1)

    scan_bench("sampler", sampler_step, (logits0, jnp.zeros((batch,), jnp.int32)))


if __name__ == "__main__":
    main()
