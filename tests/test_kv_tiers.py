"""Hierarchical KV tiers + slot suspend/resume (PR 19 tentpole).

Three layers of pinning:

* ``KVTierManager`` unit behavior — demote/lookup/LRU-to-spill round trips,
  restart ``warm_exports`` chain reassembly, and chaos containment (a
  severed Object Store mid-demotion loses only the cold copy; a faulted
  fetch is an honest miss).
* Engine bit-identity — chunks demoted out of the HBM prefix cache and
  promoted back (dense, int8 KVQ, tp=2 on the forced host devices) must
  reproduce the plain paged greedy sequence exactly, and a slot suspended
  under pool pressure (swap-don't-shed) must resume and finish with the
  ample-pool greedy tokens — including mid-spec-decode and schema-
  constrained slots, whose DFA state rides the suspended request.
* Bookkeeping — the pool is fully free after a suspend/resume storm, a
  suspended slot's deadline keeps running, and the SUSPEND chaos hook
  falls back to the honest retryable shed without stranding a refcount.
"""

import asyncio
import time

import jax
import numpy as np
import pytest

from nats_llm_studio_tpu.engine.generator import SamplingParams
from nats_llm_studio_tpu.models.config import ModelConfig
from nats_llm_studio_tpu.models.llama import init_params
from nats_llm_studio_tpu.parallel import build_mesh
from nats_llm_studio_tpu.parallel.sharding import shard_params
from nats_llm_studio_tpu.serve.batcher import BatcherOverloaded, ContinuousBatcher
from nats_llm_studio_tpu.serve.constrain import TokenDFA
from nats_llm_studio_tpu.serve.kv_tiers import (
    KVTierManager,
    MemorySpillStore,
    path_hash,
)
from nats_llm_studio_tpu.transport import faults

from conftest import async_test


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig.tiny(n_layers=2, max_seq_len=128)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompt(n, mul=7, add=3, vocab=509):
    return [(i * mul + add) % vocab for i in range(n)]


async def _serve(b, prompts, n, constrain=None):
    sp = SamplingParams(temperature=0.0, max_tokens=n)

    async def one(p):
        return [t async for t in b.submit(p, sp, constrain=constrain)]

    return await asyncio.gather(*[one(p) for p in prompts])


async def _wait(pred, timeout=10.0, what="condition"):
    t0 = time.monotonic()
    while not pred():
        if time.monotonic() - t0 > timeout:
            raise AssertionError(f"timed out waiting for {what}")
        await asyncio.sleep(0.002)


# -- KVTierManager unit behavior ---------------------------------------------


def _leaves(seed, shape=(2, 4, 16, 8)):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(shape).astype(np.float32),
            rng.standard_normal(shape).astype(np.float32))


def test_tier_demote_lookup_and_lru_spill_roundtrip():
    """Host LRU honors the byte budget by evicting to the spill store; the
    evicted entry comes back through ``lookup`` as a fetched blob."""
    store = MemorySpillStore()
    k, v = _leaves(1)
    entry_bytes = 2 * k.nbytes
    m = KVTierManager(2 * entry_bytes + 64, chunk_tokens=16, spill=store,
                      namespace="kv/t")
    try:
        keys = [tuple(range(i * 16, i * 16 + 16)) for i in range(3)]
        for i, key in enumerate(keys):
            ki, vi = _leaves(10 + i)
            assert m.demote(key, ki, vi, None)
        assert m.flush(), "spill thread did not drain"
        st = m.stats()
        # 3 demoted into a 2-entry budget: the LRU (keys[0]) spilled
        assert st["demoted_chunks"] == 3
        assert st["host_entries"] == 2
        assert st["host_evictions"] == 1 and st["spilled_blobs"] == 1
        # host hit refreshes recency
        assert m.lookup(keys[2]) is not None
        assert m.stats()["host_hits"] == 1
        # the spilled key round-trips: miss in host, fetched from the store
        got = m.lookup(keys[0])
        assert got is not None
        k0, _ = _leaves(10)
        assert np.array_equal(got.k, k0)
        st = m.stats()
        assert st["fetched_blobs"] == 1 and st["fetch_failures"] == 0
    finally:
        m.close()


def test_tier_warm_exports_skips_chain_with_missing_ancestor():
    """Restart reassembly only returns COMPLETE root→leaf chains: a chain
    whose ancestor blob was lost is skipped, never half-imported."""
    store = MemorySpillStore()
    a = [1] * 16, [1] * 32  # two chunk-prefix keys of chain A
    bb = [2] * 16, [2] * 32
    m = KVTierManager(0, chunk_tokens=16, spill=store, namespace="kv/w")
    try:
        for depth_keys in (a, bb):
            for key in depth_keys:
                ki, vi = _leaves(sum(key))
                m.demote(key, ki, vi, None)
        assert m.flush()
    finally:
        m.close()
    # lose chain A's root blob (index entry survives — the realistic
    # partial-failure shape after an Object Store prune or flake)
    store.delete(f"kv/w/{path_hash(tuple(a[0]))}")
    m2 = KVTierManager(0, chunk_tokens=16, spill=store, namespace="kv/w")
    try:
        exports = m2.warm_exports(limit=4)
        assert len(exports) == 1
        assert exports[0]["token_ids"] == [2] * 32
        assert len(exports[0]["chunks"]) == 2
    finally:
        m2.close()


def test_tier_spill_sever_is_contained():
    """A store severed mid-demotion loses exactly that blob: the failure is
    counted, later spills land, and the index never references a blob that
    was not written."""
    store = MemorySpillStore()
    faults.install(faults.FaultPlan().sever(faults.TIER_SPILL, 0))
    m = KVTierManager(0, chunk_tokens=16, spill=store, namespace="kv/s")
    try:
        for i in range(3):
            ki, vi = _leaves(20 + i)
            m.demote(tuple(range(i * 16, i * 16 + 16)), ki, vi, None)
        assert m.flush()
        st = m.stats()
        assert st["spill_failures"] == 1
        assert st["spilled_blobs"] == 2
    finally:
        faults.clear()
        m.close()
    import json

    idx = json.loads(store.get("kv/s/index"))
    assert len(idx) == 2
    for h in idx:
        assert store.get(f"kv/s/{h}") is not None, "index points at lost blob"


def test_tier_fetch_fault_is_honest_miss():
    """A faulted Object Store read is a counted miss, not corruption — and
    the next lookup (rule fired) succeeds."""
    store = MemorySpillStore()
    m = KVTierManager(0, chunk_tokens=16, spill=store, namespace="kv/f")
    try:
        key = tuple(range(16))
        ki, vi = _leaves(33)
        m.demote(key, ki, vi, None)
        assert m.flush()
        faults.install(faults.FaultPlan().drop(faults.TIER_FETCH, 0))
        try:
            assert m.lookup(key) is None
            assert m.stats()["fetch_failures"] == 1
        finally:
            faults.clear()
        got = m.lookup(key)
        assert got is not None and np.array_equal(got.k, ki)
    finally:
        m.close()


# -- demote → promote bit-identity through the engine ------------------------


def _tiered_batcher(params, cfg, mesh=None, spill=None, host_bytes=32 << 20,
                    **kw):
    b = ContinuousBatcher(params, cfg, max_slots=2, max_seq_len=64,
                          buckets=[8, 64], mesh=mesh, prefill_chunk=16,
                          prefix_cache_blocks=2, paged=True, **kw)
    b.kv_tiers = KVTierManager(host_bytes, chunk_tokens=b.prefill_chunk,
                               spill=spill, namespace="kv/test")
    return b


async def _demote_promote_cycle(b, p, q, n=6):
    """Serve P (caches 2 chunks), serve Q (evicts P's chunks → demote),
    re-serve P (promotion-on-hit). Returns (first, second) token lists."""
    first = (await _serve(b, [p], n))[0]
    await _serve(b, [q], n)
    second = (await _serve(b, [p], n))[0]
    return first, second


@async_test
async def test_demote_promote_bit_identity_dense(model):
    cfg, params = model
    p, q = _prompt(40), _prompt(40, mul=11, add=5)
    base = ContinuousBatcher(params, cfg, max_slots=2, max_seq_len=64,
                             buckets=[8, 64], prefill_chunk=16, paged=True)
    try:
        want = (await _serve(base, [p], 6))[0]
    finally:
        base.stop()
    b = _tiered_batcher(params, cfg)
    try:
        first, second = await _demote_promote_cycle(b, p, q)
        assert first == want
        assert second == want
        st = b.kv_tiers.stats()
        assert st["demoted_chunks"] >= 2, st
        assert st["promoted_chunks"] >= 2, st
        assert b.prefix_cache.hit_tokens >= 32
    finally:
        b.stop()


@pytest.mark.slow
@async_test
async def test_demote_promote_bit_identity_kvq(model):
    """int8 KV chunks demote as (codes, scales) pairs and promote back
    bit-identically against the same quantized engine without tiers."""
    cfg, params = model
    cfg_q = cfg.with_(kv_quant="int8")
    p, q = _prompt(40), _prompt(40, mul=11, add=5)
    base = ContinuousBatcher(params, cfg_q, max_slots=2, max_seq_len=64,
                             buckets=[8, 64], prefill_chunk=16, paged=True)
    try:
        want = (await _serve(base, [p], 6))[0]
    finally:
        base.stop()
    b = _tiered_batcher(params, cfg_q)
    try:
        first, second = await _demote_promote_cycle(b, p, q)
        assert first == want
        assert second == want
        assert b.kv_tiers.stats()["promoted_chunks"] >= 2
    finally:
        b.stop()


@pytest.mark.slow
@async_test
async def test_demote_promote_bit_identity_tp2(model):
    """Promotion writes land in the tp-sharded pool (re-pinned sharding)
    and still reproduce the unsharded greedy sequence."""
    cfg, params = model
    p, q = _prompt(40), _prompt(40, mul=11, add=5)
    base = ContinuousBatcher(params, cfg, max_slots=2, max_seq_len=64,
                             buckets=[8, 64], prefill_chunk=16, paged=True)
    try:
        want = (await _serve(base, [p], 6))[0]
    finally:
        base.stop()
    mesh = build_mesh("tp=2", devices=jax.devices()[:2])
    sharded = shard_params(params, mesh, cfg)
    b = _tiered_batcher(sharded, cfg, mesh=mesh)
    try:
        first, second = await _demote_promote_cycle(b, p, q)
        assert first == want
        assert second == want
        assert b.kv_tiers.stats()["promoted_chunks"] >= 2
    finally:
        b.stop()


# -- slot suspend/resume: swap-don't-shed ------------------------------------

# deterministic pool-pressure geometry (32-token blocks, max_seq 64 → at
# most 2 blocks per row, so NO slot ever grows mid-decode):
#   usable pool = 3 blocks; A (33-token prompt) admits with 2, decodes;
#   B (40-token prompt) needs 2 — its second chunk alloc fails with 1 free,
#   suspends A (frees 2), B admits and finishes, A resumes and finishes.
_SUSPEND_KW = dict(max_slots=2, max_seq_len=64, buckets=[8, 64],
                   prefill_chunk=32, kv_block_tokens=32, kv_pool_blocks=3,
                   decode_burst=1, admit_coalesce_ms=0.0, paged=True)


async def _pressure_pair(b, pa, pb, na, nb, constrain=None):
    """A first; once 2 of A's tokens arrived, B — whose admit exhausts the
    3-block pool. Returns (a_tokens, b_tokens)."""
    spa = SamplingParams(temperature=0.0, max_tokens=na)
    spb = SamplingParams(temperature=0.0, max_tokens=nb)
    started = asyncio.get_running_loop().create_future()

    async def run_a():
        out = []
        async for t in b.submit(pa, spa, constrain=constrain):
            out.append(t)
            if len(out) == 2 and not started.done():
                started.set_result(None)
        return out

    async def run_b():
        return [t async for t in b.submit(pb, spb, constrain=constrain)]

    ta = asyncio.ensure_future(run_a())
    await started
    tb = asyncio.ensure_future(run_b())
    return await ta, await tb


@async_test
async def test_suspend_resume_greedy_bit_identity(model):
    cfg, params = model
    pa, pb = _prompt(33), _prompt(40, mul=11, add=5)
    ample = ContinuousBatcher(params, cfg, **{**_SUSPEND_KW,
                                              "kv_pool_blocks": 0})
    try:
        want_a, want_b = await _serve(ample, [pa, pb], 12)
        want_b = want_b[:8]
    finally:
        ample.stop()
    b = ContinuousBatcher(params, cfg, **_SUSPEND_KW)
    try:
        got_a, got_b = await _pressure_pair(b, pa, pb, 12, 8)
        assert got_a == want_a, "suspended slot did not resume bit-identically"
        assert got_b == want_b
        assert b._suspend_stats["suspended_total"] >= 1
        assert b._suspend_stats["resumed_total"] >= 1
        assert b.stats.shed_cause_counts().get("kv_pool", 0) == 0
        await _wait(lambda: b.idle, what="slots drained")
        st = b.pool_stats()
        assert st["blocks_free"] == st["blocks_total"], st
    finally:
        b.stop()


@pytest.mark.slow
@async_test
async def test_suspend_resume_mid_spec_decode(model):
    """The spec-decode slot mirror (draft state, rng steps) rides the
    suspended record; resume continues the exact greedy sequence."""
    cfg, params = model
    pa = ([3, 4, 5] * 9 + _prompt(6, mul=13))[:33]  # repetition: drafts hit
    pb = _prompt(40, mul=11, add=5)
    kw = {**_SUSPEND_KW, "spec_decode_k": 4}
    ample = ContinuousBatcher(params, cfg, **{**kw, "kv_pool_blocks": 0})
    try:
        want_a, want_b = await _serve(ample, [pa, pb], 12)
        want_b = want_b[:8]
    finally:
        ample.stop()
    b = ContinuousBatcher(params, cfg, **kw)
    try:
        got_a, got_b = await _pressure_pair(b, pa, pb, 12, 8)
        assert got_a == want_a
        assert got_b == want_b
        assert b._suspend_stats["suspended_total"] >= 1
        assert b.stats.shed_cause_counts().get("kv_pool", 0) == 0
    finally:
        b.stop()


class _EvenCharDFA:
    """Char DFA whose alphabet is just 'e': lifted over a vocabulary where
    even token ids map to 'e' and odd ids to no surface string, it bans
    every odd token forever — a real mask the ext decode path must apply
    on every step, before and after the suspension."""

    start = 0

    def step(self, state, ch):
        return 0 if ch == "e" else None

    def accepting(self, state):
        return True


def _even_dfa(vocab):
    strings = ["e" if t % 2 == 0 else None for t in range(vocab)]
    return TokenDFA(_EvenCharDFA(), strings, vocab, frozenset())


@pytest.mark.slow
@async_test
async def test_suspend_resume_constrained_slot(model):
    """A schema-constrained (ext-regime) slot suspends and resumes with its
    DFA state intact: output stays all-even and bit-identical."""
    cfg, params = model
    dfa = _even_dfa(cfg.vocab_size)
    pa, pb = _prompt(33), _prompt(40, mul=11, add=5)
    ample = ContinuousBatcher(params, cfg, **{**_SUSPEND_KW,
                                              "kv_pool_blocks": 0})
    try:
        want_a, want_b = await _serve(ample, [pa, pb], 12, constrain=dfa)
        want_b = want_b[:8]
    finally:
        ample.stop()
    b = ContinuousBatcher(params, cfg, **_SUSPEND_KW)
    try:
        got_a, got_b = await _pressure_pair(b, pa, pb, 12, 8, constrain=dfa)
        assert got_a == want_a and all(t % 2 == 0 for t in got_a)
        assert got_b == want_b
        assert b._suspend_stats["suspended_total"] >= 1
    finally:
        b.stop()


@pytest.mark.slow
@async_test
async def test_pool_fully_free_after_suspend_resume_storm(model):
    """Six no-growth requests over a 6-block pool on 4 slots: admissions
    must suspend victims (never shed), every request finishes with the
    ample-pool tokens, and the pool is fully free at the end."""
    cfg, params = model
    prompts = [_prompt(40, mul=5 + i, add=i) for i in range(6)]
    ample = ContinuousBatcher(params, cfg, max_slots=4, max_seq_len=64,
                              buckets=[8, 64], prefill_chunk=16, paged=True)
    try:
        want = await _serve(ample, prompts, 8)
    finally:
        ample.stop()
    b = ContinuousBatcher(params, cfg, max_slots=4, max_seq_len=64,
                          buckets=[8, 64], prefill_chunk=16,
                          kv_pool_blocks=6, prefix_cache_blocks=2,
                          decode_burst=1, admit_coalesce_ms=0.0, paged=True)
    b.kv_tiers = KVTierManager(1 << 20, chunk_tokens=b.prefill_chunk)
    try:
        # max_tokens=8 keeps every row at exactly 3 blocks (48 tokens, no
        # growth) while keeping slots live long enough that later admits
        # in the same group hit a genuinely occupied pool
        got = await _serve(b, prompts, 8)
        assert got == want
        assert b._suspend_stats["suspended_total"] >= 1
        assert b._suspend_stats["resumed_total"] == \
            b._suspend_stats["suspended_total"]
        assert b.stats.shed_cause_counts().get("kv_pool", 0) == 0
        await _wait(lambda: b.idle, what="slots drained")
        assert not b._suspended
        b.drop_prefix_cache()
        st = b.pool_stats()
        assert st["blocks_free"] == st["blocks_total"], st
        assert st["blocks_live"] == 0
    finally:
        b.stop()


@async_test
async def test_suspended_slot_deadline_keeps_running(model):
    """Brownout/deadline interaction: parking a slot does not stop its
    clock — an expired suspended request is failed with the retryable
    deadline cause instead of resuming into a blown budget."""
    cfg, params = model
    pa, pb = _prompt(33), _prompt(40, mul=11, add=5)
    b = ContinuousBatcher(params, cfg, **_SUSPEND_KW)
    try:
        spa = SamplingParams(temperature=0.0, max_tokens=20)
        spb = SamplingParams(temperature=0.0, max_tokens=20)
        started = asyncio.get_running_loop().create_future()

        async def run_a():
            out = []
            async for t in b.submit(pa, spa):
                out.append(t)
                if len(out) == 2 and not started.done():
                    started.set_result(None)
            return out

        ta = asyncio.ensure_future(run_a())
        await started
        tb = asyncio.ensure_future(_serve(b, [pb], 20))
        await _wait(lambda: b._suspended, what="slot suspension")
        # the clock ran out while parked (owner sweeps suspended slots
        # every tick, so this is observed before any resume)
        b._suspended[0].req.deadline = time.monotonic() - 1.0
        with pytest.raises(BatcherOverloaded) as ei:
            await ta
        assert "deadline exceeded while suspended" in str(ei.value)
        assert "retry" in str(ei.value)
        await tb  # the admit that caused the suspension still serves
        assert b._suspend_stats["suspended_deadline_expired"] == 1
        assert b.stats.shed_cause_counts().get("deadline", 0) == 1
        await _wait(lambda: b.idle, what="slots drained")
        st = b.pool_stats()
        assert st["blocks_free"] == st["blocks_total"], st
    finally:
        b.stop()


@async_test
async def test_suspend_fault_falls_back_to_retryable_shed(model):
    """Chaos SUSPEND drop (worker dying mid-suspend): the victim slot is
    untouched, the admit that needed its blocks sheds honestly retryable,
    and no refcount is stranded."""
    cfg, params = model
    pa, pb = _prompt(33), _prompt(40, mul=11, add=5)
    b = ContinuousBatcher(params, cfg, **_SUSPEND_KW)
    faults.install(faults.FaultPlan().drop(faults.SUSPEND, 0))
    try:
        # 24 tokens (33 + 24 stay inside A's two blocks): with 12, a loaded
        # machine let A finish before B's admit arrived, and nothing shed
        spa = SamplingParams(temperature=0.0, max_tokens=24)
        started = asyncio.get_running_loop().create_future()

        async def run_a():
            out = []
            async for t in b.submit(pa, spa):
                out.append(t)
                if len(out) == 2 and not started.done():
                    started.set_result(None)
            return out

        ta = asyncio.ensure_future(run_a())
        await started
        with pytest.raises(BatcherOverloaded) as ei:
            await _serve(b, [pb], 8)
        assert "retry" in str(ei.value)
        got_a = await ta  # the would-be victim kept decoding untouched
        assert len(got_a) == 24
        assert b._suspend_stats["suspend_failures"] >= 1
        assert b._suspend_stats["suspended_total"] == 0
        assert b.stats.shed_cause_counts().get("kv_pool", 0) == 1
        await _wait(lambda: b.idle, what="slots drained")
        st = b.pool_stats()
        assert st["blocks_free"] == st["blocks_total"], st
    finally:
        faults.clear()
        b.stop()


@pytest.mark.slow
@async_test
async def test_decode_growth_exhaustion_suspends_grower(model):
    """Mid-decode table growth that finds the pool empty parks the growing
    slot (zero lost work) instead of shedding it — it resumes, regrows,
    and finishes with the ample-pool greedy tokens.

    16-token blocks, usable pool = 4: A (20-token prompt, 14 new) admits
    with 2 blocks and must grow a 3rd at position 32; B (17-token prompt,
    15 new) admits with 2 and never grows. Both decode in lockstep, so A's
    growth hits free=0 while B is still live."""
    cfg, params = model
    pa = _prompt(20)
    pb = _prompt(17, mul=11, add=5)
    kw = dict(max_slots=2, max_seq_len=64, buckets=[8, 64], prefill_chunk=16,
              decode_burst=1, admit_coalesce_ms=0.0, paged=True)
    ample = ContinuousBatcher(params, cfg, **kw)
    try:
        spa = SamplingParams(temperature=0.0, max_tokens=14)
        spb = SamplingParams(temperature=0.0, max_tokens=15)
        want_a = [t async for t in ample.submit(pa, spa)]
        want_b = [t async for t in ample.submit(pb, spb)]
    finally:
        ample.stop()
    b = ContinuousBatcher(params, cfg, kv_pool_blocks=4, **kw)
    try:
        spa = SamplingParams(temperature=0.0, max_tokens=14)
        spb = SamplingParams(temperature=0.0, max_tokens=15)

        async def run(p, sp):
            return [t async for t in b.submit(p, sp)]

        got_a, got_b = await asyncio.gather(run(pa, spa), run(pb, spb))
        assert got_a == want_a, "grower did not resume bit-identically"
        assert got_b == want_b
        assert b._suspend_stats["suspended_total"] >= 1
        assert b._suspend_stats["resumed_total"] >= 1
        assert b.stats.shed_cause_counts().get("kv_pool", 0) == 0
        await _wait(lambda: b.idle, what="slots drained")
        st = b.pool_stats()
        assert st["blocks_free"] == st["blocks_total"], st
    finally:
        b.stop()


@async_test
async def test_decode_growth_exhaustion_sheds_without_cache_reset(model):
    """A lone slot whose full extent exceeds the pool can never be parked
    profitably: its growth failure is an honest retryable shed of THAT
    request only — no cache reset (pool epoch stays 0) and the engine
    keeps serving."""
    cfg, params = model
    b = ContinuousBatcher(params, cfg, max_slots=2, max_seq_len=64,
                          buckets=[8, 64], prefill_chunk=16,
                          kv_pool_blocks=2, decode_burst=1,
                          admit_coalesce_ms=0.0, paged=True)
    try:
        sp = SamplingParams(temperature=0.0, max_tokens=14)
        with pytest.raises(BatcherOverloaded) as ei:
            [t async for t in b.submit(_prompt(20), sp)]
        assert "retry" in str(ei.value)
        assert b.stats.shed_cause_counts().get("kv_pool", 0) == 1
        # a follow-up that fits serves normally on the same cache
        sp2 = SamplingParams(temperature=0.0, max_tokens=4)
        out = [t async for t in b.submit(_prompt(10), sp2)]
        assert len(out) == 4
        await _wait(lambda: b.idle, what="slots drained")
        st = b.pool_stats()
        assert st["epoch"] == 0, "growth exhaustion must not reset the cache"
        assert st["blocks_free"] == st["blocks_total"], st
    finally:
        b.stop()


# -- promotion chaos + restart-with-warm-cache -------------------------------


@async_test
async def test_fetch_fault_during_promotion_keeps_serving(model):
    """A severed Object Store mid-promotion degrades to a plain prefill:
    same tokens, a counted fetch failure, no wedged admit."""
    cfg, params = model
    p, q = _prompt(40), _prompt(40, mul=11, add=5)
    store = MemorySpillStore()
    b = _tiered_batcher(params, cfg, spill=store, host_bytes=0)
    try:
        first = (await _serve(b, [p], 6))[0]
        await _serve(b, [q], 6)
        assert b.kv_tiers.flush()
        faults.install(faults.FaultPlan().drop(faults.TIER_FETCH, 0))
        try:
            second = (await _serve(b, [p], 6))[0]
        finally:
            faults.clear()
        assert second == first
        assert b.kv_tiers.stats()["fetch_failures"] >= 1
    finally:
        b.stop()


@async_test
async def test_restart_with_object_store_warm_cache(model):
    """Process-death survival: a FRESH engine + tier manager over the same
    spill store (no live donor) warm-imports the spilled chains and serves
    the repeat prompt with prefix hits and identical tokens."""
    cfg, params = model
    p, q = _prompt(40), _prompt(40, mul=11, add=5)
    store = MemorySpillStore()
    b1 = _tiered_batcher(params, cfg, spill=store, host_bytes=0)
    try:
        want = (await _serve(b1, [p], 6))[0]
        await _serve(b1, [q], 6)
    finally:
        b1.stop()  # close() flushes pending spills into the store
    assert len(store) > 1, "nothing spilled for the restart to import"

    b2 = _tiered_batcher(params, cfg, spill=store, host_bytes=0)
    try:
        b2.start()
        warm_tokens = 0
        for export in b2.kv_tiers.warm_exports(limit=4):
            warm_tokens += int(b2.import_prefix_blocks(export).get("tokens", 0))
        assert warm_tokens >= 32, "warm import covered no spilled chains"
        hit0 = b2.prefix_cache.hit_tokens
        got = (await _serve(b2, [p], 6))[0]
        assert got == want
        assert b2.prefix_cache.hit_tokens - hit0 >= 32
    finally:
        b2.stop()
