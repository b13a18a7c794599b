"""The plain latent-attention block through the live batcher: long prompts
through chunk groups of several rows over key blocks far smaller than the
window, then decode bursts through the pool and the table, token for token
against the plain reference at the toy of ``tests/test_mla_moe_plain.py``.
A file of its own so that ``--dist loadfile`` gives it a worker of its own."""

import asyncio

import numpy as np
from conftest import async_test
from test_mla_moe_plain import CONF, REF, seeded

from nats_llm_studio_tpu.models import mla_moe

SEQ = 256


def _held_to_the_reference(params, prompt, served):
    """Every served token is the reference's best at its position (float32:
    the margin of a toy's argmax is far over the paths' 2e-4)."""
    ref = REF.tail_logprobs(params, CONF, list(prompt) + served[:-1], len(served))
    gaps = [float(ref[i].max() - ref[i, t]) for i, t in enumerate(served)]
    assert max(gaps) < 1e-3, gaps


def tokens(seed: int, n: int) -> list[int]:
    return [int(t) for t in np.random.default_rng(seed).integers(32, 127, size=n)]


@async_test(timeout=300.0)  # the admit, chunk-group and burst programs on an empty compile cache
async def test_long_prompts_through_chunk_groups_then_bursts_token_for_token(monkeypatch):
    """Five prompts of 20 to 201 tokens over four slots at a chunk of 32 and
    key blocks of 64 in a window of 256: groups of several rows whose prompts
    end in different chunks, a single chunked admit behind them, every row
    decoding in bursts beside the others' chunks. Each launch says what it
    attended over, each burst what it read."""
    from nats_llm_studio_tpu.engine.generator import SamplingParams
    from nats_llm_studio_tpu.obs import spans
    from nats_llm_studio_tpu.serve import batcher as bt

    monkeypatch.setattr(mla_moe, "_K_BLOCK", 64)
    monkeypatch.setenv("DECODE_KERNEL", "pallas")   # off the chip: through the interpreter
    cfg, params = seeded(CONF, REF, seq=SEQ)
    reqs = [(tokens(60 + i, n), m) for i, (n, m) in enumerate(
        [(150, 9), (97, 14), (201, 6), (60, 11), (20, 5)])]
    spans.clear()
    b = bt.ContinuousBatcher(params, cfg, max_slots=4, max_seq_len=SEQ, buckets=[16, 32],
                             prefill_chunk=32, decode_burst=4, max_group_long=4)
    try:
        assert b.decode_kernel == "pallas" and b.paged

        async def one(p, m):
            return [t async for t in b.submit(p, SamplingParams(temperature=0.0, max_tokens=m))]

        got = await asyncio.gather(*(one(p, m) for p, m in reqs))
        for (p, m), toks in zip(reqs, got):
            assert len(toks) == m
            _held_to_the_reference(params, p, toks)
        admits = [a for _, _, _, a in spans.records(0.0, float("inf"), "batcher.admit") if a]
        chunks = [a for a in admits if a.get("program") == "chunk"]
        long = [len(p) for p, _ in reqs if len(p) > 32]
        # every token of a chunked prompt went through exactly one launch
        assert sum(a["tokens"] for a in chunks) == sum(long)
        assert any(a["rows"] > 1 for a in chunks), "a group of several rows ran"
        # a row's keys end at its own frontier: summed over its launches,
        # n // 32 whole chunks and the rest
        want_keys = sum(sum(range(32, n + 1, 32)) + (n if n % 32 else 0) for n in long)
        assert sum(a["live_keys"] for a in chunks) == want_keys
        assert all(a["rows"] * 1 <= a["tokens"] <= a["rows"] * 32 for a in chunks)
        assert all(a["pairs"] <= a["tokens"] * a["live_keys"] for a in chunks)
        bursts = [a for _, _, _, a in spans.records(0.0, float("inf"), "batcher.readback")
                  if a and a.get("program") == "decode"]
        assert bursts and all("live_tokens" in a and "expert_steps" in a for a in bursts)
        assert max(a["live_tokens"] for a in bursts) >= 201
    finally:
        b.stop()
