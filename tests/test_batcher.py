"""Continuous batcher tests: batched greedy decode must reproduce
single-stream generation exactly; slots admit/release mid-flight;
oversubscription queues (SURVEY.md §7 hard part #5)."""

import asyncio

import jax
import pytest

from nats_llm_studio_tpu.engine.generator import Generator, SamplingParams
from nats_llm_studio_tpu.models.config import ModelConfig
from nats_llm_studio_tpu.models.llama import init_params
from nats_llm_studio_tpu.serve.batcher import ContinuousBatcher

from conftest import async_test


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig.tiny(n_layers=2, max_seq_len=64)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def reference_greedy(cfg, params, prompt, n):
    gen = Generator(params, cfg, max_seq_len=64, buckets=[8, 16, 32, 64])
    sp = SamplingParams(temperature=0.0, max_tokens=n)
    return [t for t, _ in gen.generate(prompt, sp)]


@async_test
async def test_concurrent_greedy_matches_single_stream(model):
    cfg, params = model
    prompts = [[1, 2, 3], [9, 8, 7, 6], [5], [10, 20, 30, 40, 50]]
    want = [reference_greedy(cfg, params, p, 6) for p in prompts]

    b = ContinuousBatcher(params, cfg, max_slots=4, max_seq_len=64, buckets=[8, 64])
    try:
        async def run(p):
            sp = SamplingParams(temperature=0.0, max_tokens=6)
            return [t async for t in b.submit(p, sp)]

        got = await asyncio.gather(*[run(p) for p in prompts])
        assert list(got) == want
    finally:
        b.stop()


@async_test
async def test_join_mid_generation(model):
    cfg, params = model
    a, c = [1, 2, 3], [4, 5, 6, 7]
    want_a = reference_greedy(cfg, params, a, 8)
    want_c = reference_greedy(cfg, params, c, 8)

    b = ContinuousBatcher(params, cfg, max_slots=2, max_seq_len=64, buckets=[8, 64])
    try:
        got_a: list[int] = []
        got_c: list[int] = []

        async def run_a():
            sp = SamplingParams(temperature=0.0, max_tokens=8)
            async for t in b.submit(a, sp):
                got_a.append(t)

        async def run_c_later():
            while len(got_a) < 2:  # join after A has streamed a couple tokens
                await asyncio.sleep(0.01)
            sp = SamplingParams(temperature=0.0, max_tokens=8)
            async for t in b.submit(c, sp):
                got_c.append(t)

        await asyncio.gather(run_a(), run_c_later())
        assert got_a == want_a
        assert got_c == want_c
    finally:
        b.stop()


@async_test
async def test_oversubscription_queues(model):
    cfg, params = model
    prompts = [[i + 1, i + 2] for i in range(6)]
    want = [reference_greedy(cfg, params, p, 4) for p in prompts]
    b = ContinuousBatcher(params, cfg, max_slots=2, max_seq_len=64, buckets=[8, 64])
    try:
        async def run(p):
            sp = SamplingParams(temperature=0.0, max_tokens=4)
            return [t async for t in b.submit(p, sp)]

        got = await asyncio.gather(*[run(p) for p in prompts])
        assert list(got) == want
        assert b.stats.requests == 6
        assert b.stats.peak_active <= 2
    finally:
        b.stop()


@async_test
async def test_stop_ids_and_max_tokens(model):
    cfg, params = model
    b = ContinuousBatcher(params, cfg, max_slots=2, max_seq_len=64, buckets=[8, 64])
    try:
        first = reference_greedy(cfg, params, [3, 4], 1)[0]
        sp = SamplingParams(temperature=0.0, max_tokens=8, stop_ids=frozenset({first}))
        out = [t async for t in b.submit([3, 4], sp)]
        assert out == []  # first token is the stop token
        sp2 = SamplingParams(temperature=0.0, max_tokens=3)
        out2 = [t async for t in b.submit([3, 4], sp2)]
        assert len(out2) == 3
    finally:
        b.stop()


@async_test
async def test_prompt_too_long_raises(model):
    cfg, params = model
    b = ContinuousBatcher(params, cfg, max_slots=2, max_seq_len=16, buckets=[8, 16])
    try:
        with pytest.raises(ValueError):
            async for _ in b.submit(list(range(1, 20)), SamplingParams()):
                pass
    finally:
        b.stop()


@async_test
async def test_seeded_sampling_reproducible_across_batch_composition(model):
    """A seeded request must reproduce its completion token-for-token no
    matter what else shares the batch (per-row fold_in PRNG)."""
    cfg, params = model
    b = ContinuousBatcher(params, cfg, max_slots=4, max_seq_len=64, buckets=[8, 64])
    try:
        sp = SamplingParams(temperature=1.5, max_tokens=6, seed=1234)

        async def seeded():
            return [t async for t in b.submit([2, 3, 4], sp)]

        alone = await seeded()
        # same request again, now alongside three noisy neighbours
        noise = SamplingParams(temperature=2.0, max_tokens=12)
        crowd = await asyncio.gather(
            seeded(),
            *[
                _collect(b, [9 + i, 8, 7], noise)
                for i in range(3)
            ],
        )
        assert crowd[0] == alone
    finally:
        b.stop()


async def _collect(b, prompt, sp):
    return [t async for t in b.submit(prompt, sp)]


@async_test
async def test_chunked_prefill_matches_single_shot(model):
    """A prompt longer than prefill_chunk must produce the same greedy
    continuation as the unchunked reference (chunk boundaries exercise the
    start_pos > 0 prefill path)."""
    cfg, params = model
    prompt = [(i * 7 + 3) % cfg.vocab_size for i in range(25)]
    want = reference_greedy(cfg, params, prompt, 6)
    b = ContinuousBatcher(
        params, cfg, max_slots=2, max_seq_len=64, buckets=[8, 64], prefill_chunk=8
    )
    try:
        sp = SamplingParams(temperature=0.0, max_tokens=6)
        got = [t async for t in b.submit(prompt, sp)]
        assert got == want
    finally:
        b.stop()


@async_test
async def test_chunked_prefill_interleaves_decode(model):
    """While a long prompt is admitted in chunks, an already-active stream
    must keep receiving tokens — at least one per chunk boundary, not zero
    until the whole prefill finishes (VERDICT round-1 weak #4)."""
    cfg, params = model
    b = ContinuousBatcher(
        params, cfg, max_slots=2, max_seq_len=64, buckets=[8, 64], prefill_chunk=8
    )
    try:
        events: list[tuple[str, int]] = []
        sp_a = SamplingParams(temperature=0.0, max_tokens=40)

        async def stream_a():
            async for t in b.submit([1, 2, 3], sp_a):
                events.append(("a", t))

        task_a = asyncio.create_task(stream_a())
        # let A admit and produce a couple of tokens
        while sum(1 for k, _ in events if k == "a") < 2:
            await asyncio.sleep(0.01)
        long_prompt = [(i * 5 + 1) % cfg.vocab_size for i in range(30)]  # 4 chunks

        async def stream_b():
            sp = SamplingParams(temperature=0.0, max_tokens=4)
            async for t in b.submit(long_prompt, sp):
                events.append(("b", t))

        await stream_b()
        await task_a
        # tokens A received after B's admit started but before B's first token
        idx_b = next(i for i, (k, _) in enumerate(events) if k == "b")
        a_before = sum(1 for k, _ in events[:idx_b] if k == "a")
        # B's prompt spans 4 chunks -> >= 3 interleaved decode steps; allow
        # scheduling slack but require genuine interleaving
        assert a_before >= 4, events
        # B's admit interleaved with decode steps that ADVANCED the ring:
        # its output must still match the single-stream reference (catches
        # prefix/ring misalignment, not just scheduling)
        b_toks = [t for k, t in events if k == "b"]
        assert b_toks == reference_greedy(cfg, params, long_prompt, 4)
    finally:
        b.stop()


@async_test
async def test_chunked_prefill_flash_continuation_matches(model):
    """With use_flash_attention on, chunk continuations ride the
    cache-backed flash kernel (interpret mode on CPU) — output must still
    match the dense single-stream reference exactly."""
    cfg, params = model
    fcfg = cfg.with_(use_flash_attention=True)
    prompts = [
        [(i * 7 + 3) % cfg.vocab_size for i in range(25)],
        [(i * 5 + 1) % cfg.vocab_size for i in range(30)],
    ]
    want = [reference_greedy(cfg, params, p, 5) for p in prompts]
    b = ContinuousBatcher(
        params, fcfg, max_slots=2, max_seq_len=64, buckets=[8, 64],
        prefill_chunk=8, max_group_long=2,
    )
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


@async_test
async def test_chunked_group_admit_deterministic(model):
    """Concurrent LONG prompts (each > prefill_chunk, mixed lengths across
    chunk boundaries) form ONE batched chunked admit and every stream must
    match the single-stream reference — pins the per-row end-chunk logit
    select, per-row ring shifts, and the batched finish."""
    cfg, params = model
    prompts = [
        [(i * 7 + 3) % cfg.vocab_size for i in range(25)],   # 4 chunks
        [(i * 5 + 1) % cfg.vocab_size for i in range(30)],   # 4 chunks
        [(i * 3 + 2) % cfg.vocab_size for i in range(17)],   # 3 chunks
        [(i * 11 + 5) % cfg.vocab_size for i in range(9)],   # 2 chunks
    ]
    want = [reference_greedy(cfg, params, p, 5) for p in prompts]
    b = ContinuousBatcher(
        params, cfg, max_slots=4, max_seq_len=64, buckets=[8, 64],
        prefill_chunk=8, max_group_long=4,
    )
    try:
        async def run(p):
            sp = SamplingParams(temperature=0.0, max_tokens=5)
            return [t async for t in b.submit(p, sp)]

        tasks = [asyncio.create_task(run(p)) for p in prompts]
        await asyncio.sleep(0)  # all enqueued before the owner thread starts
        got = await asyncio.gather(*tasks)
        assert list(got) == want
        assert b.stats.chunked_group_admits >= 2, b.stats.snapshot()
    finally:
        b.stop()


@async_test
async def test_chunked_group_admit_interleaves_and_spares_live_stream(model):
    """A batched chunked admit must (a) keep a live stream decoding at
    chunk boundaries, (b) deliver it NO junk from the reserved rows, and
    (c) produce reference-exact output for the grouped long prompts even
    though interleaved decodes moved the ring mid-admit."""
    cfg, params = model
    b = ContinuousBatcher(
        params, cfg, max_slots=3, max_seq_len=64, buckets=[8, 64],
        prefill_chunk=8, max_group_long=2,
    )
    try:
        events: list[tuple[str, int]] = []
        sp_a = SamplingParams(temperature=0.0, max_tokens=44)

        async def stream_a():
            async for t in b.submit([1, 2, 3], sp_a):
                events.append(("a", t))

        task_a = asyncio.create_task(stream_a())
        while sum(1 for k, _ in events if k == "a") < 2:
            await asyncio.sleep(0.01)
        longs = [
            [(i * 5 + 1) % cfg.vocab_size for i in range(30)],
            [(i * 9 + 4) % cfg.vocab_size for i in range(27)],
        ]
        want = [reference_greedy(cfg, params, p, 4) for p in longs]

        async def stream_long(tag, p):
            sp = SamplingParams(temperature=0.0, max_tokens=4)
            async for t in b.submit(p, sp):
                events.append((tag, t))

        await asyncio.gather(*(stream_long(f"l{i}", p)
                               for i, p in enumerate(longs)))
        await task_a
        assert b.stats.chunked_group_admits == 2, b.stats.snapshot()
        # (a) live stream kept flowing during the grouped admit
        idx_l = next(i for i, (k, _) in enumerate(events) if k.startswith("l"))
        a_before = sum(1 for k, _ in events[:idx_l] if k == "a")
        assert a_before >= 4, events
        # (b)+(c) exact reference outputs — junk delivery or ring
        # misalignment would break these
        for i, w in enumerate(want):
            assert [t for k, t in events if k == f"l{i}"] == w
        # the live stream's own output is also reference-exact
        assert [t for k, t in events if k == "a"] == reference_greedy(
            cfg, params, [1, 2, 3], 44
        )
    finally:
        b.stop()


@async_test
async def test_group_admit_deterministic(model):
    """Force the batched-admission path deterministically: fill the inbox
    BEFORE starting the owner thread so all requests form one group, and
    check every stream against the single-stream reference (pins the
    per-row offset/placement/last-logit math, including mixed lengths in
    one bucket and pad-rows-repeat-row-0)."""
    cfg, params = model
    prompts = [[1, 2, 3], [9, 8, 7, 6], [5], [10, 20, 30, 40, 50], [2, 4]]
    want = [reference_greedy(cfg, params, p, 5) for p in prompts]
    b = ContinuousBatcher(params, cfg, max_slots=8, max_seq_len=64, buckets=[8, 64])
    try:
        async def run(p):
            sp = SamplingParams(temperature=0.0, max_tokens=5)
            return [t async for t in b.submit(p, sp)]

        # enqueue all submissions in one loop tick; the batcher thread starts
        # on the first submit and drains the inbox as one waitlist -> one
        # grouped admit (5 requests -> mpad 8, 3 pad rows repeating row 0)
        tasks = [asyncio.create_task(run(p)) for p in prompts]
        await asyncio.sleep(0)  # let every submit enqueue before work starts
        got = await asyncio.gather(*tasks)
        assert list(got) == want
        assert b.stats.requests == len(prompts)
        # the batched path must actually have run — without this the test
        # could silently degrade to admit_one coverage on timing changes
        assert b.stats.grouped_admits >= 2, b.stats.snapshot()
    finally:
        b.stop()


@async_test
async def test_wide_group_admit_deterministic(model):
    """max_group_admit above 8 (throughput-tuned deployments): 16 requests
    form ONE [16, bucket] fused admit and every stream still matches the
    single-stream reference; the queue-delay metric records one entry per
    request."""
    cfg, params = model
    prompts = [[i + 1, i + 2, i % 5 + 1] for i in range(16)]
    want = [reference_greedy(cfg, params, p, 4) for p in prompts]
    b = ContinuousBatcher(params, cfg, max_slots=16, max_seq_len=64,
                          buckets=[8, 64], max_group_admit=16)
    try:
        async def run(p):
            sp = SamplingParams(temperature=0.0, max_tokens=4)
            return [t async for t in b.submit(p, sp)]

        tasks = [asyncio.create_task(run(p)) for p in prompts]
        await asyncio.sleep(0)
        got = await asyncio.gather(*tasks)
        assert list(got) == want
        assert b.stats.grouped_admits >= 9, b.stats.snapshot()  # wide path ran
        assert b.stats.admit_delay_ms.count == len(prompts)
        snap = b.stats.snapshot()
        assert snap["admit_queue_delay_p95_ms"] >= snap["admit_queue_delay_p50_ms"] >= 0.0
    finally:
        b.stop()


@async_test
async def test_ring_wrap_compaction_restores_windows(model):
    """Drive the shared ring past wrap with a live stream, drain to one
    slot, and assert (a) the compaction fired and cleared the wrapped flag,
    (b) the surviving stream's greedy tokens still match the single-stream
    reference — i.e. the on-device roll re-aligned every live row's
    validity window exactly (VERDICT r2 weak #7 recovery path)."""
    cfg, params = model
    S = 256
    cfg = cfg.with_(max_seq_len=S)
    buckets = [8, 16, 32, 64, 128, S]
    long_p, short_p = [1, 2, 3], [4, 5, 6, 7]
    gen = Generator(params, cfg, max_seq_len=S, buckets=buckets)
    want_long = [t for t, _ in gen.generate(long_p, SamplingParams(temperature=0.0, max_tokens=248))]
    want_short = [t for t, _ in gen.generate(short_p, SamplingParams(temperature=0.0, max_tokens=60))]

    # paged=False: this test exercises the legacy ring layout's wrap +
    # compaction machinery, which the paged block pool replaces outright
    b = ContinuousBatcher(params, cfg, max_slots=2, max_seq_len=S,
                          buckets=buckets, paged=False)
    try:
        got_long: list[int] = []
        got_short: list[int] = []

        async def run_long():
            # A drives the ring head to ~251; its ~56-token tail after B's
            # trigger gives B several burst-records of margin to overlap
            sp = SamplingParams(temperature=0.0, max_tokens=248)
            async for t in b.submit(long_p, sp):
                got_long.append(t)

        async def run_short_late():
            # join near the wrap with a SMALL pos; survive the wrap (which
            # lands just after A exits), then the compaction re-rolls the
            # ring around B's live window. Trigger at 192/248: late enough
            # that B's 60 tokens span the wrap, early enough that B's admit
            # beats A's exit even when a loaded CI host starves the loop
            while len(got_long) < 192:
                await asyncio.sleep(0.001)
            sp = SamplingParams(temperature=0.0, max_tokens=60)
            async for t in b.submit(short_p, sp):
                got_short.append(t)

        await asyncio.gather(run_long(), run_short_late())
        assert b.stats.peak_active == 2, b.stats.snapshot()  # streams overlapped
        assert b.stats.ring_compactions >= 1, b.stats.snapshot()
        assert b._ring_wrapped is False
        assert got_long == want_long
        assert got_short == want_short
    finally:
        b.stop()


@async_test
async def test_idle_full_prefill_matches(model):
    """An idle engine admits a long prompt through prefill_full (one fresh
    dispatch at a pow2 token bucket, right-padded) instead of chunking.
    Output must equal the single-stream reference at several lengths
    straddling bucket edges, and a FOLLOWING admit while the first stream
    decodes must still be correct (the rolled-in pad junk above n lands on
    future ring slots decode overwrites — never in any validity window).
    Flash is on (interpret-mode kernels on CPU): the shortcut is gated on
    the fresh-flash path, since the dense fallback's [Hq, bucket, S] score
    matrix is exactly what chunking exists to bound."""
    cfg, params = model
    fcfg = cfg.with_(use_flash_attention=True)
    b = ContinuousBatcher(
        params, fcfg, max_slots=2, max_seq_len=64, buckets=[8, 64], prefill_chunk=4
    )
    try:
        for ln in (5, 9, 31, 38):  # bucket edges: 8|16|32|64
            p = [(i * 7 + 3 + ln) % cfg.vocab_size for i in range(ln)]
            want = reference_greedy(cfg, params, p, 5)
            sp = SamplingParams(temperature=0.0, max_tokens=5)
            got = [t async for t in b.submit(p, sp)]
            assert got == want, (ln, got, want)
        # pad-junk check: long idle admit, then a joiner decodes alongside
        p1 = [(i * 5 + 1) % cfg.vocab_size for i in range(21)]  # bucket 32
        p2 = [4, 5, 6]
        want1 = reference_greedy(cfg, params, p1, 16)
        want2 = reference_greedy(cfg, params, p2, 8)
        got1: list[int] = []

        async def first():
            async for t in b.submit(p1, SamplingParams(temperature=0.0, max_tokens=16)):
                got1.append(t)

        t1 = asyncio.create_task(first())
        while len(got1) < 2:
            await asyncio.sleep(0.01)
        got2 = [t async for t in b.submit(p2, SamplingParams(temperature=0.0, max_tokens=8))]
        await t1
        assert got1 == want1
        assert got2 == want2
    finally:
        b.stop()


@pytest.mark.parametrize("wide", [True, False], ids=["wide_burst", "narrow_burst"])
def test_a_wide_bursts_tokens_of_a_row_reach_its_stream_in_one_hand_over(model, wide):
    """A decode burst that hands over more than ``_TOKEN_HANDOVERS_A_BURST``
    tokens gives a row's n tokens to its stream as ONE event (one wake-up of
    the event loop), not n: at 24 live rows x 8 tokens the per-token
    hand-overs stretched the owner thread's way from a readback to its next
    intake until a closed loop's next request raced it (PR 34). A burst
    under the mark keeps a hand-over a token, as the 8-slot cells measure it.
    Either way the stream's tokens are the single-stream reference's."""
    from nats_llm_studio_tpu.serve import batcher as bt

    cfg, params = model
    prompt, n = [3, 1, 4, 1, 5, 9, 2, 6], 13
    want = reference_greedy(cfg, params, prompt, n)
    kinds: list[tuple[str, int]] = []
    sound, mark = bt._Request.emit, bt._TOKEN_HANDOVERS_A_BURST

    def noting(self, kind, value):
        kinds.append((kind, len(value) if kind == "toks" else 1))
        sound(self, kind, value)

    async def stream():
        b = ContinuousBatcher(params, cfg, max_slots=2, max_seq_len=64, decode_burst=4)
        try:
            sp = SamplingParams(temperature=0.0, max_tokens=n)
            return [batch async for batch in b.submit_batched(prompt, sp)]
        finally:
            b.stop()

    bt._Request.emit = noting
    bt._TOKEN_HANDOVERS_A_BURST = 3 if wide else mark  # one row x four steps
    try:
        batches = asyncio.run(stream())
    finally:
        bt._Request.emit, bt._TOKEN_HANDOVERS_A_BURST = sound, mark
    assert [t for batch in batches for t in batch] == want
    # the admit's first token alone, then three bursts of four, then the end
    burst = [("toks", 4)] if wide else [("tok", 1)] * 4
    assert kinds == [("tok", 1), *burst * 3, ("end", 1)]


# -- a chunked group admit narrows as its prompts end --------------------------
#
# Four prompts of 2, 3, 5 and 9 chunks in ONE group of four: the launches run
# 4, 4, 4 (three live rows keep the width-4 program), 2, 2, 1, 1, 1, 1 rows
# wide, each row is finished and decodes once its own prompt has ended, and
# its tokens are those it gets when admitted alone.

NARROW_CHUNK, NARROW_SEQ = 16, 160
NARROW_LENS = (21, 40, 70, 140)
NARROW_WIDTHS = [4, 4, 4, 2, 2, 1, 1, 1, 1]
NARROW_CASES = [  # family, paged, prefix_cache_blocks
    ("dense", True, 0), ("dense", False, 0), ("dense", True, 16), ("dense", False, 16),
    ("latent", True, 0), ("window", True, 0), ("state", True, 0),
]


def _narrow_id(case):
    family, paged, cache = case
    return f"{family}-{'paged' if paged else 'ring'}{'-prefix_cache' if cache else ''}"


@pytest.fixture(scope="module", params=NARROW_CASES, ids=_narrow_id)
def narrow_case(request):
    from test_scopes import _cfg  # the families' toys

    family, paged, cache = request.param
    cfg = _cfg(family, NARROW_SEQ)
    params = init_params(cfg, jax.random.PRNGKey(0))

    def batcher():
        return ContinuousBatcher(
            params, cfg, max_slots=4, max_seq_len=NARROW_SEQ, buckets=[16, 32],
            prefill_chunk=NARROW_CHUNK, max_group_long=4, paged=paged, kv_block_tokens=16,
            prefix_cache_blocks=cache)

    prompts = [[(i * (7 + 2 * k) + 3 + k) % 95 + 32 for i in range(n)]
               for k, n in enumerate(NARROW_LENS)]
    # two greedy rows and two that draw under their own seed
    sps = [SamplingParams(temperature=0.0 if k % 2 else 0.9, max_tokens=6, seed=100 + k)
           for k in range(len(prompts))]
    return batcher, prompts, sps


def _watch_chunk_launches(b) -> list:
    """(rows wide, start, {prompt length: tokens streamed so far}) of every
    chunk launch of ``b``, group program and ``prefill1`` alike, in order. The
    streamed counts are the owner thread's own, read as it launches."""
    from nats_llm_studio_tpu.serve.batcher import _Request

    launches: list = []
    streamed: dict[int, int] = {}

    def watch(inner):
        def run(params, tokens, km, vm, start, *rest, **kw):
            for r in b._slots:
                if isinstance(r, _Request):
                    streamed[len(r.prompt_ids)] = r.generated
            launches.append((tokens.shape[0], int(start[0]), dict(streamed)))
            return inner(params, tokens, km, vm, start, *rest, **kw)

        return run

    b._prefill_chunk_group = watch(b._prefill_chunk_group)
    b._prefill1 = watch(b._prefill1)
    return launches


async def _one_group(b, prompts, sps):
    tasks = [asyncio.create_task(_collect(b, p, sp)) for p, sp in zip(prompts, sps)]
    await asyncio.sleep(0)  # all enqueued before the owner thread starts
    return await asyncio.gather(*tasks, return_exceptions=True)


@async_test(timeout=300.0)  # a family's admit, chunk and burst programs on an empty compile cache
async def test_a_chunked_group_narrows_as_its_prompts_end(narrow_case):
    from nats_llm_studio_tpu.obs import spans

    batcher, prompts, sps = narrow_case
    b = batcher()
    launches = _watch_chunk_launches(b)
    spans.clear()
    try:
        got = await _one_group(b, prompts, sps)
        group = list(launches)
        records = [a for _, _, _, a in spans.records(0.0, float("inf"), "batcher.admit")
                   if a and a.get("program") == "chunk"]
        snap = b.stats.snapshot()
        b.drop_prefix_cache()  # alone means alone: no row of the group to hit
        alone = [await _collect(b, p, sp) for p, sp in zip(prompts, sps)]
    finally:
        b.stop()
    assert got == alone and all(len(t) == 6 for t in alone)
    assert [w for w, _, _ in group] == NARROW_WIDTHS
    assert [s for _, s, _ in group] == [j * NARROW_CHUNK for j in range(len(NARROW_WIDTHS))]
    # the two short rows have streamed tokens before the longest row's last chunk
    before_last = group[-1][2]
    assert before_last.get(NARROW_LENS[0], 0) > 0 and before_last.get(NARROW_LENS[1], 0) > 0
    assert NARROW_LENS[3] not in before_last
    real = [4, 4, 3, 2, 2, 1, 1, 1, 1]
    assert snap["chunked_group_admits"] == 4
    assert snap["chunk_rows_computed"] == sum(NARROW_WIDTHS)
    assert snap["chunk_rows_real"] == sum(real)
    assert snap["chunked_group_narrowings"] == 2 and snap["chunked_group_early_finishes"] == 3
    if b.cfg.is_mla:  # the latent families' chunk records say it too
        assert [a["width"] for a in records] == NARROW_WIDTHS
        assert [a["rows"] for a in records] == real
    # alone, every launch is one row wide
    assert {w for w, _, _ in launches[len(group):]} == {1}


@async_test(timeout=300.0)
async def test_a_failed_launch_after_an_early_finish_fails_every_row_once(narrow_case):
    """The third launch fails (the 2-chunk row is installed and decoding by
    then): the rows still prefilling get the launch's error and give their
    slots back, the installed row fails through the reset like any live
    request, and the batcher serves the next request."""
    from nats_llm_studio_tpu.serve.batcher import _RESERVED

    batcher, prompts, sps = narrow_case
    b = batcher()
    inner, calls = b._prefill_chunk_group, []

    def failing(*args, **kw):
        calls.append(1)
        if len(calls) == 3:
            raise RuntimeError("injected launch failure")
        return inner(*args, **kw)

    b._prefill_chunk_group = failing
    try:
        got = await _one_group(b, prompts, sps)
        assert all(isinstance(e, RuntimeError) for e in got), got
        assert "cache reset after a failed device dispatch" in str(got[0])
        assert all("injected launch failure" in str(e) for e in got[1:])
        assert b.stats.chunked_group_admits == 1 and b.stats.chunked_group_early_finishes == 1
        for _ in range(500):  # the reset gives the slots back after it has failed their rows
            if all(s is None for s in b._slots):
                break
            await asyncio.sleep(0.01)
        assert all(s is None for s in b._slots) and _RESERVED not in b._slots
        if b.paged:
            assert b.pool_stats()["blocks_free"] == b.pool_stats()["blocks_total"]
        again = await _collect(b, prompts[0], sps[0])
        assert len(again) == 6
    finally:
        b.stop()
