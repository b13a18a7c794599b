"""int8 KV cache (ops/kvcache.py, cfg.kv_quant="int8"): quantized-cache
serving must stay numerically faithful and internally consistent.

Tiers: codec roundtrip; forward-vs-fp closeness; EXACT consistency between
chunked prefill / incremental decode and single-shot quantized prefill (the
same values quantize identically wherever they land); batcher greedy vs the
independent Generator oracle, both quantized (the serving hot path: ring
writes, fused admits, rolls, compaction all preserve codes+scales)."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nats_llm_studio_tpu.engine.generator import Generator, SamplingParams
from nats_llm_studio_tpu.models.config import ModelConfig
from nats_llm_studio_tpu.models.llama import forward, init_params, make_cache
from nats_llm_studio_tpu.ops.kvcache import KVQ, quantize_rows
from nats_llm_studio_tpu.serve.batcher import ContinuousBatcher

from conftest import async_test


def _cfg(**kw):
    base = dict(n_layers=2, max_seq_len=64, kv_quant="int8")
    base.update(kw)
    return ModelConfig.tiny(**base)


def test_quantize_rows_roundtrip():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 16), jnp.float32) * 4.0
    kv = quantize_rows(x)
    assert kv.q.dtype == jnp.int8 and kv.s.shape == (3, 5)
    back = kv.q.astype(jnp.float32) * kv.s[..., None]
    # absmax int8: worst-case error is amax/254 per element
    amax = np.abs(np.asarray(x)).max(axis=-1, keepdims=True)
    assert (np.abs(np.asarray(back) - np.asarray(x)) <= amax / 254 + 1e-7).all()
    # zero rows stay exactly zero (scale guard against /0)
    z = quantize_rows(jnp.zeros((2, 4)))
    assert (np.asarray(z.q) == 0).all()


def test_forward_close_to_fp_cache():
    cfg = _cfg()
    params = init_params(cfg.with_(kv_quant="none"), jax.random.PRNGKey(1))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 9), 0, cfg.vocab_size)
    start = jnp.zeros((2,), jnp.int32)

    kf, vf = make_cache(cfg.with_(kv_quant="none"), 2, 32)
    want, _, _ = forward(params, cfg.with_(kv_quant="none"), tokens, kf, vf, start)

    kq, vq = make_cache(cfg, 2, 32)
    assert isinstance(kq, KVQ)
    got, kq, vq = forward(params, cfg, tokens, kq, vq, start)
    # int8 KV is approximate; logits stay close and the argmax agrees
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0.15, atol=0.15)
    assert (np.asarray(got[:, -1].argmax(-1)) == np.asarray(want[:, -1].argmax(-1))).all()


def test_incremental_decode_consistent_with_single_shot():
    """Prefill + per-token decode over the quantized cache must EXACTLY
    match a single-shot quantized prefill of the same sequence: identical
    values quantize identically wherever they are written."""
    cfg = _cfg()
    params = init_params(cfg.with_(kv_quant="none"), jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(4), (1, 12), 0, cfg.vocab_size)

    k1, v1 = make_cache(cfg, 1, 32)
    want, _, _ = forward(params, cfg, tokens, k1, v1, jnp.zeros((1,), jnp.int32))

    k2, v2 = make_cache(cfg, 1, 32)
    logits, k2, v2 = forward(params, cfg, tokens[:, :6], k2, v2,
                             jnp.zeros((1,), jnp.int32))
    np.testing.assert_allclose(np.asarray(logits[:, -1]), np.asarray(want[:, 5]),
                               rtol=2e-5, atol=2e-5)
    for i in range(6, 12):
        logits, k2, v2 = forward(params, cfg, tokens[:, i : i + 1], k2, v2,
                                 jnp.full((1,), i, jnp.int32))
        np.testing.assert_allclose(
            np.asarray(logits[:, -1]), np.asarray(want[:, i]),
            rtol=2e-5, atol=2e-5, err_msg=f"pos {i}",
        )


@async_test
async def test_batcher_quantized_matches_generator_oracle():
    """The serving hot path end-to-end on a quantized cache: ring-aligned
    fused admits, batched decode, rolls — greedy tokens must equal the
    naive Generator's, itself running the same quantized math."""
    cfg = _cfg()
    params = init_params(cfg.with_(kv_quant="none"), jax.random.PRNGKey(5))
    prompts = [[1, 2, 3], [9, 8, 7, 6], [5], [10, 20, 30]]

    gen = Generator(params, cfg, max_seq_len=64, buckets=[8, 64])
    want = [
        [t for t, _ in gen.generate(p, SamplingParams(temperature=0.0, max_tokens=6))]
        for p in prompts
    ]

    b = ContinuousBatcher(params, cfg, max_slots=4, max_seq_len=64, buckets=[8, 64])
    try:
        async def run(p):
            sp = SamplingParams(temperature=0.0, max_tokens=6)
            return [t async for t in b.submit(p, sp)]

        got = await asyncio.gather(*(run(p) for p in prompts))
        assert list(got) == want
    finally:
        b.stop()


@async_test
async def test_ring_compaction_quantized():
    """Wrap + compaction on the quantized ring: the roll must move codes
    AND scales together (a mismatch would corrupt every surviving row)."""
    cfg = _cfg(max_seq_len=256)
    params = init_params(cfg.with_(kv_quant="none"), jax.random.PRNGKey(6))
    buckets = [8, 16, 32, 64, 128, 256]
    gen = Generator(params, cfg, max_seq_len=256, buckets=buckets)
    want_long = [t for t, _ in gen.generate([1, 2, 3], SamplingParams(temperature=0.0, max_tokens=248))]
    want_short = [t for t, _ in gen.generate([4, 5, 6, 7], SamplingParams(temperature=0.0, max_tokens=60))]

    # paged=False: ring wrap/compaction is legacy-layout machinery; the
    # paged pool never rolls (tested in test_paged_kv.py instead)
    b = ContinuousBatcher(params, cfg, max_slots=2, max_seq_len=256,
                          buckets=buckets, paged=False)
    try:
        got_long, got_short = [], []

        async def run_long():
            sp = SamplingParams(temperature=0.0, max_tokens=248)
            async for t in b.submit([1, 2, 3], sp):
                got_long.append(t)

        async def run_short_late():
            # late enough that the short stream is live when the ring wraps
            # at 256, early enough that a loaded machine (tier 1 runs six
            # workers) admits it before the long stream's 248th token
            while len(got_long) < 200:
                await asyncio.sleep(0.002)
            sp = SamplingParams(temperature=0.0, max_tokens=60)
            async for t in b.submit([4, 5, 6, 7], sp):
                got_short.append(t)

        await asyncio.gather(run_long(), run_short_late())
        assert b.stats.peak_active == 2
        assert b.stats.ring_compactions >= 1
        assert got_long == want_long
        assert got_short == want_short
    finally:
        b.stop()


@async_test
async def test_chunked_flash_kvq_continuation_matches_oracle():
    """Chunked prefill with use_flash_attention + int8 KV routes chunk
    continuations through the quantized chunk kernel
    (flash_attention_chunk_kvq, per-tile VMEM dequant) — greedy output must
    still equal the Generator oracle running the same quantized math
    through the dense path."""
    cfg = _cfg(use_flash_attention=True)
    params = init_params(cfg.with_(kv_quant="none"), jax.random.PRNGKey(5))
    # > prefill_chunk so continuations run; group of 2 exercises the
    # batched [m, C] chunk dispatch too
    prompts = [
        [(i * 7 + 3) % cfg.vocab_size for i in range(25)],
        [(i * 5 + 1) % cfg.vocab_size for i in range(30)],
    ]
    gen = Generator(params, cfg.with_(use_flash_attention=False),
                    max_seq_len=64, buckets=[8, 64])
    want = [
        [t for t, _ in gen.generate(p, SamplingParams(temperature=0.0, max_tokens=5))]
        for p in prompts
    ]
    b = ContinuousBatcher(params, cfg, max_slots=2, max_seq_len=64,
                          buckets=[8, 64], prefill_chunk=8, max_group_long=2)
    try:
        async def run(p):
            sp = SamplingParams(temperature=0.0, max_tokens=5)
            return [t async for t in b.submit(p, sp)]

        tasks = [asyncio.create_task(run(p)) for p in prompts]
        await asyncio.sleep(0)
        got = await asyncio.gather(*tasks)
        assert list(got) == want
    finally:
        b.stop()
