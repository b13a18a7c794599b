"""The state-space family with layers of latent experts through the live
batcher: slots that finish and refill, chunked admits that carry state and
tail, a replayed position, a chunked group that narrows, against the plain
reference at the toy of ``tests/test_ssm_latent_moe.py`` (a live router, a
share of the experts). A file of its own so that ``--dist loadfile`` gives it a
worker of its own."""

import asyncio
import time

from conftest import async_test
from test_ssm_latent_moe import CONF, REF, SEQ, model, prompt, tokens  # noqa: F401 — fixtures

from nats_llm_studio_tpu.models import ssm_hybrid


def _held_to_the_reference(params, prompt, served):  # noqa: F811
    """Every served token is the reference's best at its position, or within
    float32's order of sums of it (``tests/test_ssm_latent_moe.py``'s limits:
    ~1e-4 on logits of size 30)."""
    ref = REF.tail_logprobs(params, CONF, list(prompt) + served[:-1], len(served))
    gaps = [float(ref[i].max() - ref[i, t]) for i, t in enumerate(served)]
    assert max(gaps) < 1e-3, gaps


@async_test(timeout=300.0)  # every admit and decode program on an empty compile cache
async def test_two_slots_finish_and_refill_at_different_steps_through_the_live_batcher(model):  # noqa: F811
    """Five requests of unequal prompts and lengths over two slots: group
    admits, chunked admits (prompts over the chunk of 16: the state and the
    tail carried from chunk to chunk), slots that finish and are given to the
    next request at different steps, every one decoding on its own state; the
    expert counters, the picks and the state counters ride the readback span
    and the metrics page."""
    from nats_llm_studio_tpu.engine.generator import SamplingParams
    from nats_llm_studio_tpu.obs import spans
    from nats_llm_studio_tpu.serve import batcher as bt

    cfg, params = model
    t0 = time.perf_counter()  # the span ring is the process's: other files' bursts lie before
    reqs = [(tokens(30 + i, n), m) for i, (n, m) in enumerate(
        [(9, 12), (40, 5), (21, 9), (37, 4), (12, 7)])]
    b = bt.ContinuousBatcher(params, cfg, max_slots=2, max_seq_len=SEQ, buckets=[16, 32, 64],
                             prefill_chunk=16, prefix_cache_blocks=8, spec_decode_k=4)
    try:
        assert b.decode_kernel == "pallas" and b.prefix_cache is None and b.spec_cfg is None
        assert set(b.refusals) == {"prefix_cache", "spec_decode"}
        assert "no snapshot" in b.refusals["prefix_cache"]
        assert b.stats.expert_path == "hit_list"   # 2 slots x top-6 < 32 experts

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
        assert st["state_admits_carried"] == 3  # the prompts over one chunk of 16
        pool = b.pool_stats()["state"]
        assert pool["slots_total"] == 2
        assert pool["bytes"] == 2 * ssm_hybrid.state_bytes_per_slot(cfg)
        burst = [a for _, _, _, a in spans.records(t0, float("inf"), "batcher.readback")
                 if a and "state_steps" in a]
        assert burst and all(0 < a["state_rows"] <= 2 * a["state_steps"] for a in burst)
        # three expert layers a step, top-6 a live row, a quarter of the experts held here
        assert all(a["expert_steps"] == 3 * a["state_steps"] for a in burst)
        assert all(a["moe_picks"] == 6 * a["expert_rows"] == 18 * a["state_rows"] for a in burst)
        assert all(0 <= a["moe_picks_held"] <= a["moe_picks"] for a in burst)
        assert all(a["experts_hit"] <= a["moe_picks_held"] for a in burst)
        picks = b.stats.picks_counters()
        assert 0.05 < picks["picks_held"] / picks["picks"] < 0.6, picks
        # a chunk launch's record carries its rows, tokens and pairs
        chunks = [a for _, _, _, a in spans.records(t0, float("inf"), "batcher.admit")
                  if a and a.get("program") == "chunk"]
        assert chunks and all({"rows", "tokens", "pairs"} <= set(a) for a in chunks)
        from test_moe_grouped_served import page_of

        page = page_of(b)
        value = lambda name: next(  # noqa: E731
            float(ln.rsplit(" ", 1)[1]) for ln in page.splitlines() if ln.startswith(name + "{"))
        assert value("lmstudio_moe_picks_total") == picks["picks"]
        assert value("lmstudio_moe_picks_held_total") == picks["picks_held"]
        assert value("lmstudio_ssm_state_slots_moved_total") == st["state_slots_moved"]
        assert value("lmstudio_ssm_state_pool_bytes") == pool["bytes"]
        refused = [ln for ln in page.splitlines() if ln.startswith("lmstudio_feature_refused{")]
        assert len(refused) == 2 and all('cause="off: ' in ln for ln in refused), refused
    finally:
        b.stop()


@async_test(timeout=240.0)
async def test_a_request_with_logprobs_replays_its_last_prompt_position(model, prompt):  # noqa: F811
    """The ext path: the admit's token is dropped and the last prompt position
    decoded again under the mask; the state must not consume it twice."""
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


@async_test(timeout=300.0)  # the family's admit, chunk and burst programs on an empty compile cache
async def test_a_wide_decode_takes_the_grouped_form_and_serves_the_same_tokens(model):  # noqa: F811
    """Eight slots x top-6 = 48 picks against 32 experts: ``expert_path`` sends
    the DECODE step to the grouped form (what the cell's 64 rows x top-22
    against 512 do); the tokens are the reference's all the same."""
    from nats_llm_studio_tpu.engine.generator import SamplingParams
    from nats_llm_studio_tpu.serve import batcher as bt

    cfg, params = model
    b = bt.ContinuousBatcher(params, cfg, max_slots=8, max_seq_len=SEQ, buckets=[16, 32, 64],
                             prefill_chunk=16)
    try:
        assert b.stats.expert_path == "grouped"
        reqs = [(tokens(70 + i, 10 + 3 * i), 5) for i in range(3)]

        async def one(p, m):
            return [t async for t in b.submit(p, SamplingParams(temperature=0.0, max_tokens=m))]

        for (p, m), toks in zip(reqs, await asyncio.gather(*(one(p, m) for p, m in reqs))):
            _held_to_the_reference(params, p, toks)
    finally:
        b.stop()


@async_test(timeout=300.0)
async def test_a_chunked_group_of_four_narrows_and_each_row_takes_its_state_with_it():
    """``tests/test_batcher.py``'s narrowing case for this family: four prompts
    of 2, 3, 5 and 9 chunks in ONE group; the launches run 4, 4, 4, 2, 2, 1, 1,
    1, 1 rows wide, a row is finished and decodes once its own prompt has
    ended, ``take_rows`` carries the state and the convolution tail of the rows
    that go on, and every row's tokens are those it gets when admitted alone."""
    import jax
    from test_batcher import (
        NARROW_CHUNK, NARROW_LENS, NARROW_SEQ, NARROW_WIDTHS, _collect, _one_group,
        _watch_chunk_launches)
    from test_scopes import _cfg

    from nats_llm_studio_tpu.engine.generator import SamplingParams
    from nats_llm_studio_tpu.models.llama import init_params
    from nats_llm_studio_tpu.serve.batcher import ContinuousBatcher

    cfg = _cfg("latent_experts", NARROW_SEQ)
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
        alone = [await _collect(b, p, sp) for p, sp in zip(prompts, sps)]
    finally:
        b.stop()
    assert got == alone and all(len(t) == 6 for t in alone)
    assert [w for w, _, _ in group] == NARROW_WIDTHS


def test_a_row_taken_out_of_its_slot_and_written_to_another_resumes_its_stream_at_eight_groups():
    """The path a chunked admit and a settle take (``serve/programs.py``: a
    row out of a pool or a group by ``state_row``, into a slot by
    ``state_write_row``, each leaf on the axis ``WithState.axes`` names: the
    tails' rows lie on axis 2 of [Lm, K, rows, C]), at 8 groups of two heads:
    a request prefilled in three chunks decodes 8 steps in slot 1, its state,
    tail and ``seen`` are taken out, written to slot 2 and slot 1's zeroed,
    and its next 8 steps from slot 2 are the 8 it gives when left where it
    was."""
    import jax.numpy as jnp
    import numpy as np
    from test_ssm_hybrid import grouped_model
    from test_ssm_latent_moe import decode, empty_pools, entry, into_pool, prefill

    from nats_llm_studio_tpu.ops.kvcache import WithState, state_row, state_write_row

    cfg, params = grouped_model(8)
    assert cfg.ssm_n_groups == 8 and ssm_hybrid.K_AXES == (2, 0)
    p = tokens(1, 40)
    logits, rows = prefill(cfg, params, p, chunks=(17, 17, 6))
    began, pools, _ = decode(cfg, params, into_pool(empty_pools(cfg), rows), entry(logits),
                             len(p), 8)
    stayed, _, _ = decode(cfg, params, pools, began[-1], len(p) + 8, 8)
    moved = []
    for pool in pools:
        row = state_row(pool, 1)
        assert [r.shape[ax] for r, ax in zip(row, pool.axes)] == [1] * len(row)
        there = WithState(pool.kv, state_write_row(pool, row, 2), pool.axes)
        moved.append(WithState(pool.kv, state_write_row(
            there, tuple(jnp.zeros_like(r) for r in row), 1), pool.axes))
        for a, b in zip(state_row(moved[-1], 2), row):
            np.testing.assert_array_equal(a, b)
    resumed, _, _ = decode(cfg, params, tuple(moved), began[-1], len(p) + 8, 8, slot=2)
    assert [e["bytes"] for e in resumed] == [e["bytes"] for e in stayed]
    np.testing.assert_allclose(
        [[t["logprob"] for t in e["top_logprobs"]] for e in resumed],
        [[t["logprob"] for t in e["top_logprobs"]] for e in stayed], rtol=0, atol=1e-5)
    assert len({e["bytes"][0] for e in stayed}) > 3   # a stream, not one token again and again
