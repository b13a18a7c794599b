"""The window / full attention family through the live batcher: slots that
retire and refill, chunked admits, a replayed position, QoS suspend and
resume with the rings, against the plain reference at the toy of
``tests/test_swa_moe.py``. A file of its own so that ``--dist loadfile``
gives it a worker of its own."""

import asyncio
import time

from conftest import async_test, hold_decodes_until_queued
from test_swa_moe import CONF, REF, SEQ, T, WINDOW, model, prompt, tokens  # noqa: F401 — fixtures

from nats_llm_studio_tpu.models import swa_moe

# -- through the live batcher --------------------------------------------------


def _held_to_the_reference(params, prompt, served):
    """Every served token is the reference's best at its position (float32:
    the margin of a toy's argmax is far over the paths' 1e-5)."""
    ref = REF.tail_logprobs(params, CONF, list(prompt) + served[:-1], len(served))
    gaps = [float(ref[i].max() - ref[i, t]) for i, t in enumerate(served)]
    assert max(gaps) < 1e-3, gaps


@async_test(timeout=240.0)  # every admit and decode program: 28 s alone on an empty compile cache, 64 s beside five workers
async def test_two_slots_finish_and_refill_at_different_steps_through_the_live_batcher(model):
    """Five requests of unequal prompts and lengths over two slots: group
    admits, chunked admits that carry the ring across the window's edge
    (prompts over the chunk of 16), slots that retire and are refilled at
    different steps, every one decoding on its own ring for more steps than
    the ring is long."""
    from nats_llm_studio_tpu.engine.generator import SamplingParams
    from nats_llm_studio_tpu.obs import spans
    from nats_llm_studio_tpu.serve import batcher as bt

    cfg, params = model
    t0 = time.perf_counter()  # the span ring is the process's: other files' bursts lie before
    reqs = [(tokens(30 + i, n), m) for i, (n, m) in enumerate(
        [(9, 22), (40, 5), (21, 19), (37, 4), (12, 7)])]
    b = bt.ContinuousBatcher(params, cfg, max_slots=2, max_seq_len=SEQ, buckets=[16, 32, 64],
                             prefill_chunk=16, prefix_cache_blocks=8, spec_decode_k=4)
    try:
        assert b.decode_kernel == "pallas" and b.prefix_cache is None and b.spec_cfg is None
        assert set(b.refusals) == {"prefix_cache", "spec_decode"}
        assert "cannot be shared by block" in b.refusals["prefix_cache"]
        assert b.stats.expert_path == "hit_list"   # 2 slots x top-2 < 16 experts

        async def one(p, m):
            return [t async for t in b.submit(p, SamplingParams(temperature=0.0, max_tokens=m))]

        got = await asyncio.gather(*(one(p, m) for p, m in reqs))
        for (p, m), toks in zip(reqs, got):
            assert len(toks) == m
            _held_to_the_reference(params, p, toks)
        st = b.stats.window_counters()
        # a row at position p reads min(p + 1, 16) keys in a window layer
        assert 0 < st["win_tokens"] <= 2 * WINDOW * st["win_steps"]
        assert st["full_tokens"] > st["win_tokens"]
        assert st["ring_tokens"] == sum(min(len(p), WINDOW) for p, _ in reqs)
        pool = b.pool_stats()["window"]
        assert pool["slots_total"] == 2 and pool["bytes"] == 2 * swa_moe.ring_bytes_per_slot(cfg)
        assert pool["kv_pool_bytes"] == b._pool.n_blocks * 2 * 2 * 2 * T * 32 * 4
        burst = [a for _, _, _, a in spans.records(t0, float("inf"), "batcher.readback")
                 if a and "win_steps" in a]
        assert burst and all(0 < a["win_tokens"] <= a["full_tokens"] for a in burst)
        # the expert counters ride the same span (``record_moe``)
        assert all(a["expert_steps"] == 4 * a["win_steps"] and a["experts_hit"] > 0 for a in burst)
        admits = [a for _, _, _, a in spans.records(t0, float("inf"), "batcher.admit") if a]
        assert sum(a["ring"] for a in admits if "ring" in a) == st["ring_tokens"]
        # the worker's page: the two kinds of cache priced apart, the
        # counters, each refusal with its cause
        from test_moe_grouped_served import page_of

        page = page_of(b)
        value = lambda name: next(  # noqa: E731
            float(ln.rsplit(" ", 1)[1]) for ln in page.splitlines() if ln.startswith(name + "{"))
        assert value("lmstudio_swa_ring_pool_bytes") == pool["bytes"]
        assert value("lmstudio_swa_full_pool_bytes") == pool["kv_pool_bytes"]
        assert value("lmstudio_swa_ring_pool_slots_total") == 2
        assert value("lmstudio_swa_win_tokens_total") == st["win_tokens"]
        assert value("lmstudio_swa_full_tokens_total") == st["full_tokens"]
        refused = [ln for ln in page.splitlines() if ln.startswith("lmstudio_feature_refused{")]
        assert len(refused) == 2 and all('cause="off: ' in ln for ln in refused), refused
    finally:
        b.stop()


@async_test
async def test_a_request_with_logprobs_replays_its_last_prompt_position(model, prompt):
    from nats_llm_studio_tpu.engine.generator import SamplingParams
    from nats_llm_studio_tpu.serve import batcher as bt

    cfg, params = model
    b = bt.ContinuousBatcher(params, cfg, max_slots=2, max_seq_len=SEQ, buckets=[16, 32, 64],
                             prefill_chunk=16)
    try:
        sp = SamplingParams(temperature=0.0, max_tokens=6)
        toks = [t[0] async for t in b.submit(prompt, sp, want_logprobs=True, top_logprobs=3)]
        _held_to_the_reference(params, prompt, toks)
    finally:
        b.stop()


@async_test(timeout=240.0)  # two batchers' programs: 64 s alone on an empty compile cache
async def test_a_preempted_slot_resumes_on_its_own_ring_and_kv(model):
    """QoS preempt-and-resume (``tests/test_qos.py``'s geometry: a pool of
    three blocks of 32, one step a dispatch): a premium admit parks the batch
    slot, its KV blocks AND its rings go to the host, another request runs
    through the slot's neighbour, and the victim's tokens after the resume are
    the reference's and those of a run that was never parked."""
    from nats_llm_studio_tpu.engine.generator import SamplingParams
    from nats_llm_studio_tpu.serve import batcher as bt

    cfg, params = model
    pa, pb = tokens(50, 33), tokens(51, 40)
    kw = dict(max_slots=2, max_seq_len=SEQ, buckets=[16, 32, 64], prefill_chunk=32,
              kv_block_tokens=32, decode_burst=1, admit_coalesce_ms=0.0, qos_preempt=True)

    async def one(b, p, m, **who):
        return [t async for t in b.submit(p, SamplingParams(temperature=0.0, max_tokens=m), **who)]

    ample = bt.ContinuousBatcher(params, cfg, **kw)
    try:
        want_a, want_b = await one(ample, pa, 12), await one(ample, pb, 8)
    finally:
        ample.stop()
    b = bt.ContinuousBatcher(params, cfg, kv_pool_blocks=3, **kw)
    try:
        hold_decodes_until_queued(b)   # A cannot finish before B has arrived
        started = asyncio.get_running_loop().create_future()

        async def run_a():
            out = []
            async for t in b.submit(pa, SamplingParams(temperature=0.0, max_tokens=12),
                                    tenant="hobby", priority="batch"):
                out.append(t)
                if len(out) == 2 and not started.done():
                    started.set_result(None)
            return out

        ta = asyncio.ensure_future(run_a())
        await started
        got_b = await one(b, pb, 8, tenant="acme", priority="premium")
        got_a = await ta
        assert b._suspend_stats["suspended_total"] >= 1 and b._suspend_stats["resumed_total"] >= 1
        assert got_a == want_a and got_b == want_b
        _held_to_the_reference(params, pa, got_a)
        _held_to_the_reference(params, pb, got_b)
    finally:
        b.stop()
