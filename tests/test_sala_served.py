"""The lightning / block-sparse family through the live batcher: slots that
finish and refill, chunked admits that carry the state and the pooled keys
past the dense length, a replayed position, QoS suspend and resume with both,
against the plain reference at the toy of ``tests/test_sala.py``. A file of
its own so that ``--dist loadfile`` gives it a worker of its own."""

import asyncio
import time

from conftest import async_test, hold_decodes_until_queued
from test_sala import CONF, REF, SEQ, model, tokens  # noqa: F401 — fixtures

from nats_llm_studio_tpu.models import sala


def _held_to_the_reference(params, prompt, served):
    """Every served token is the reference's best at its position, or within
    float32 rounding of it (``tests/test_sala.py``'s limits)."""
    ref = REF.tail_logprobs(params, CONF, list(prompt) + served[:-1], len(served))
    gaps = [float(ref[i].max() - ref[i, t]) for i, t in enumerate(served)]
    assert max(gaps) < 2e-3, gaps


@async_test(timeout=420.0)  # every admit and decode program on an empty compile cache
async def test_two_slots_finish_and_refill_at_different_steps_through_the_live_batcher(model):  # noqa: F811
    """Five requests of unequal prompts and lengths over two slots: group
    admits, chunked admits (prompts over the chunk of 32; three of them past
    the dense length of 96, one crossing it while it decodes), slots that
    finish and are given to the next request at different steps, every one on
    its own state and its own pooled keys."""
    from nats_llm_studio_tpu.engine.generator import SamplingParams
    from nats_llm_studio_tpu.obs import spans
    from nats_llm_studio_tpu.serve import batcher as bt

    cfg, params = model
    t0 = time.perf_counter()  # the span ring is the process's: other files' bursts lie before
    reqs = [(tokens(30 + i, n), m) for i, (n, m) in enumerate(
        [(9, 12), (150, 5), (91, 9), (203, 4), (120, 7)])]
    b = bt.ContinuousBatcher(params, cfg, max_slots=2, max_seq_len=SEQ, buckets=[16, 32, 64],
                             prefill_chunk=32, prefix_cache_blocks=8, spec_decode_k=4)
    try:
        assert b.decode_kernel == "pallas" and b.prefix_cache is None and b.spec_cfg is None
        assert set(b.refusals) == {"prefix_cache", "spec_decode"}
        assert "pooled keys" in b.refusals["prefix_cache"]

        async def one(p, m):
            return [t async for t in b.submit(p, SamplingParams(temperature=0.0, max_tokens=m))]

        got = await asyncio.gather(*(one(p, m) for p, m in reqs))
        for (p, m), toks in zip(reqs, got):
            assert len(toks) == m
            _held_to_the_reference(params, p, toks)
        st = b.stats.state_counters()
        assert st["state_steps"] > 0 and st["state_rows"] <= 2 * st["state_steps"]
        assert st["state_slots_moved"] == st["state_rows"]
        assert st["state_admits_fresh"] + st["state_admits_carried"] == len(reqs)
        assert st["state_admits_carried"] == 4  # the prompts over one chunk of 32
        pool = b.pool_stats()["state"]
        assert pool["slots_total"] == 2 and pool["bytes"] == 2 * sala.state_bytes_per_slot(cfg)
        burst = [a for _, _, _, a in spans.records(t0, float("inf"), "batcher.readback")
                 if a and "sparse_tokens_live" in a]
        assert burst and all(0 < a["sparse_tokens_picked"] <= a["sparse_tokens_live"]
                             for a in burst)
        sp = b.stats.sparse_counters()
        # three sparse layers; the short requests decode under the dense length
        assert 0 < sp["rows_dense"] < 3 * st["state_rows"]
        assert sp["tokens_picked"] < sp["tokens_live"]
        from test_moe_grouped_served import page_of

        page = page_of(b)
        value = lambda name: next(  # noqa: E731
            float(ln.rsplit(" ", 1)[1]) for ln in page.splitlines() if ln.startswith(name + "{"))
        assert value("lmstudio_sparse_tokens_picked_total") == sp["tokens_picked"]
        assert value("lmstudio_sparse_tokens_live_total") == sp["tokens_live"]
        assert value("lmstudio_ssm_state_slots_moved_total") == st["state_slots_moved"]
        assert value("lmstudio_ssm_state_pool_bytes") == pool["bytes"]
        refused = [ln for ln in page.splitlines() if ln.startswith("lmstudio_feature_refused{")]
        assert len(refused) == 2 and all('cause="off: ' in ln for ln in refused), refused
    finally:
        b.stop()


@async_test(timeout=300.0)
async def test_a_request_with_logprobs_replays_its_last_prompt_position(model):  # noqa: F811
    """The ext path: the admit's token is dropped and the last prompt position
    decoded again under the mask; the state must not consume it twice, and the
    sparse layer writes the same key and pooled key again."""
    from nats_llm_studio_tpu.engine.generator import SamplingParams
    from nats_llm_studio_tpu.serve import batcher as bt

    cfg, params = model
    prompt = tokens(1, 112)
    b = bt.ContinuousBatcher(params, cfg, max_slots=2, max_seq_len=SEQ, buckets=[16, 32, 64],
                             prefill_chunk=32)
    try:
        sp = SamplingParams(temperature=0.0, max_tokens=6)
        toks = [t[0] async for t in b.submit(prompt, sp, want_logprobs=True, top_logprobs=3)]
        _held_to_the_reference(params, prompt, toks)
    finally:
        b.stop()


@async_test(timeout=420.0)  # two batchers' programs on an empty compile cache
async def test_a_preempted_slot_resumes_on_its_own_state_pooled_keys_and_kv(model):  # noqa: F811
    """QoS preempt-and-resume: a premium admit parks the batch slot past the
    dense length, its KV blocks, its state row AND its pooled keys go to the
    host, another request runs, and the victim's tokens after the resume are
    the reference's and those of a run that was never parked."""
    from nats_llm_studio_tpu.engine.generator import SamplingParams
    from nats_llm_studio_tpu.serve import batcher as bt

    cfg, params = model
    pa, pb = tokens(50, 100), tokens(51, 40)
    kw = dict(max_slots=2, max_seq_len=SEQ, buckets=[16, 32, 64], prefill_chunk=32,
              kv_block_tokens=16, decode_burst=1, admit_coalesce_ms=0.0, qos_preempt=True)

    async def one(b, p, m, **who):
        return [t async for t in b.submit(p, SamplingParams(temperature=0.0, max_tokens=m), **who)]

    ample = bt.ContinuousBatcher(params, cfg, **kw)
    try:
        want_a, want_b = await one(ample, pa, 12), await one(ample, pb, 8)
    finally:
        ample.stop()
    b = bt.ContinuousBatcher(params, cfg, kv_pool_blocks=8, **kw)
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


@async_test(timeout=420.0)  # the family's admit, chunk and burst programs on an empty compile cache
async def test_a_chunked_group_of_four_narrows_and_each_row_takes_its_state_with_it():
    """``tests/test_batcher.py``'s narrowing case for this family: four prompts
    of 2, 3, 5 and 9 chunks in ONE group; the launches run 4, 4, 4, 2, 2, 1, 1,
    1, 1 rows wide, a row is finished and decodes once its own prompt has
    ended, ``take_rows`` carries the state and the pooled keys of the rows that
    go on, and every row's tokens are those it gets when admitted alone."""
    import jax
    from test_batcher import (
        NARROW_CHUNK, NARROW_LENS, NARROW_SEQ, NARROW_WIDTHS, _collect, _one_group,
        _watch_chunk_launches)
    from test_scopes import _cfg

    from nats_llm_studio_tpu.engine.generator import SamplingParams
    from nats_llm_studio_tpu.models.llama import init_params
    from nats_llm_studio_tpu.serve.batcher import ContinuousBatcher

    cfg = _cfg("lightning", NARROW_SEQ)
    params = init_params(cfg, jax.random.PRNGKey(0))
    b = ContinuousBatcher(params, cfg, max_slots=4, max_seq_len=NARROW_SEQ, buckets=[16, 32],
                          prefill_chunk=NARROW_CHUNK, max_group_long=4, kv_block_tokens=16)
    prompts = [[(i * (7 + 2 * k) + 3 + k) % 95 + 32 for i in range(n)]
               for k, n in enumerate(NARROW_LENS)]
    sps = [SamplingParams(temperature=0.0 if k % 2 else 0.9, max_tokens=6, seed=100 + k)
           for k in range(len(prompts))]
    launches = _watch_chunk_launches(b)
    try:
        got = await _one_group(b, prompts, sps)
        group = list(launches)
        snap = b.stats.snapshot()
        alone = [await _collect(b, p, sp) for p, sp in zip(prompts, sps)]
    finally:
        b.stop()
    assert got == alone and all(len(t) == 6 for t in alone)
    assert [w for w, _, _ in group] == NARROW_WIDTHS
    assert snap["chunked_group_narrowings"] == 2 and snap["chunked_group_early_finishes"] == 3
    assert {w for w, _, _ in launches[len(group):]} == {1}
