"""Multi-tenant QoS (PR 20 tentpole): API keys, weighted fair share,
priority-class brownout, and preempt-to-host-tier.

Four layers of pinning:

* serve/qos.py units — ``API_KEYS`` spec parsing (malformed entries fail
  the boot, not silently admit), token-bucket rate limiting, monthly
  usage accounting, priority-header wire round-trips, DRR weighted-share
  convergence (single tenant == exact FIFO backcompat), and the top-K +
  ``other`` cardinality cap.
* Batcher policy — brownout sheds strictly by class (batch < standard <
  premium, cause-tagged ``brownout``), a premium admit on a full pool
  preempts the lowest-class victim to the host tier and the victim
  resumes bit-identically, and tenant-less submits keep the exact
  pre-QoS anonymous/standard behavior.
* Gateway front door — 401 for missing/invalid keys, typed 429s with
  ``Retry-After`` for rate and monthly-token quota, resolved tenant/
  class stamped onto the bus headers (never the client's claim), and
  the no-API_KEYS deployment serving unauthenticated exactly as before.
* Exposition — per-tenant families on the worker renderer, the gateway
  edge counters, and the aggregator's post-merge cardinality cap
  (disjoint per-worker top-Ks must not union past K cluster-wide).
"""

import asyncio
import time

import jax
import pytest

from nats_llm_studio_tpu.engine.generator import SamplingParams
from nats_llm_studio_tpu.gateway.server import _envelope_error_response
from nats_llm_studio_tpu.models.config import ModelConfig
from nats_llm_studio_tpu.models.llama import init_params
from nats_llm_studio_tpu.obs import PromRenderer
from nats_llm_studio_tpu.obs.aggregator import merge_into
from nats_llm_studio_tpu.serve.batcher import BatcherOverloaded, ContinuousBatcher
from nats_llm_studio_tpu.serve.brownout import BROWNOUT, BrownoutConfig, SHED_ONLY
from nats_llm_studio_tpu.serve.qos import (
    ANON_TENANT,
    DEFAULT_PRIORITY,
    DrrScheduler,
    TenantStats,
    TenantUsage,
    TokenBucket,
    cap_tenant_rows,
    class_rank,
    class_weight,
    format_priority_header,
    parse_api_keys,
    parse_priority_header,
)
from nats_llm_studio_tpu.transport.envelope import (
    error_is_retryable,
    shed_cause,
    shed_cause_of,
)

from conftest import async_test, hold_decodes_until_queued
from fakes import EchoEngine, FakeRegistry
from test_gateway import CHAT, GatewayHarness


# -- API_KEYS spec parsing ---------------------------------------------------


def test_parse_api_keys_full_and_defaults():
    keys = parse_api_keys(
        "sk-a:acme:premium:2.5:10:1000000, sk-b:hobby:batch, sk-c:corp"
    )
    a = keys["sk-a"]
    assert (a.tenant, a.priority, a.weight, a.rps, a.monthly_tokens) == (
        "acme", "premium", 2.5, 10.0, 1000000)
    b = keys["sk-b"]
    assert (b.tenant, b.priority, b.weight, b.rps, b.monthly_tokens) == (
        "hobby", "batch", 0.0, 0.0, 0)
    # class defaults to standard; whitespace around entries tolerated
    assert keys["sk-c"].priority == DEFAULT_PRIORITY
    assert parse_api_keys("") == {} and parse_api_keys(None) == {}


@pytest.mark.parametrize("spec,msg", [
    ("sk-a", "key:tenant:class"),                       # no tenant
    (":acme", "key:tenant:class"),                      # empty key
    ("sk-a:acme:platinum", "platinum"),                 # unknown class
    ("sk-a:acme:premium:heavy", "numeric"),             # non-numeric weight
    ("sk-a:acme,sk-a:beta", "duplicate"),               # duplicate key
])
def test_parse_api_keys_rejects_malformed(spec, msg):
    # a half-configured auth table must fail the gateway at boot, not
    # silently admit everyone
    with pytest.raises(ValueError, match=msg):
        parse_api_keys(spec)


def test_priority_classes_rank_and_weight():
    assert class_rank("batch") < class_rank("standard") < class_rank("premium")
    assert class_weight("batch") < class_weight("standard") < class_weight("premium")
    # unknown claims clamp to standard, never premium (headers are
    # attacker-ish input on the raw-NATS path)
    assert class_rank("root") == class_rank(DEFAULT_PRIORITY)
    assert class_weight("") == class_weight(DEFAULT_PRIORITY)


def test_priority_header_roundtrip():
    assert format_priority_header("premium", 2.5) == "premium:2.5"
    assert parse_priority_header("premium:2.5") == ("premium", 2.5)
    # weight 0 = derive from class: no suffix on the wire
    assert format_priority_header("standard") == "standard"
    assert parse_priority_header("standard") == ("standard", 0.0)
    # garbage tolerated: unknown class -> standard, bad weight -> 0
    assert parse_priority_header(None) == (DEFAULT_PRIORITY, 0.0)
    assert parse_priority_header("platinum:lots") == (DEFAULT_PRIORITY, 0.0)
    assert parse_priority_header("premium:-4") == ("premium", 0.0)


# -- rate limiting + usage accounting ----------------------------------------


def test_token_bucket_burst_and_retry_after():
    tb = TokenBucket(5.0)  # burst = 2 s of rate = 10
    assert all(tb.take() for _ in range(10))
    assert not tb.take()
    assert tb.retry_after_s() > 0.0
    # zero-rate bucket admits everything (rps unset in the key spec)
    free = TokenBucket(0.0)
    assert all(free.take() for _ in range(100))
    assert free.retry_after_s() == 0.0


def test_tenant_usage_quota_and_month_roll():
    u = TenantUsage()
    assert u.charge("acme", 7) == 7
    assert u.charge("acme", 3) == 10
    assert u.tokens_used("acme") == 10 and u.tokens_used("hobby") == 0
    assert u.over_quota("acme", 10) and not u.over_quota("acme", 11)
    assert not u.over_quota("acme", 0)  # 0 = unlimited
    snap = u.snapshot()
    assert snap["acme"] == {"tokens": 10, "requests": 2}
    # crossing the month boundary resets every counter
    u._month = "1999-01"
    assert u.tokens_used("acme") == 0
    assert u.snapshot() == {}


def test_cap_tenant_rows_scalar_and_dict():
    rows = {f"t{i}": i + 1 for i in range(6)}  # t5 biggest
    capped = cap_tenant_rows(rows, 2)
    assert capped == {"t5": 6, "t4": 5, "other": 1 + 2 + 3 + 4}
    # dict-valued rows rank by total and merge key-wise into ``other``
    drows = {"a": {"served": 9, "shed": 1},
             "b": {"served": 2, "shed": 0},
             "c": {"served": 1, "shed": 5}}
    dcap = cap_tenant_rows(drows, 1)
    assert dcap == {"a": {"served": 9, "shed": 1},
                    "other": {"served": 3, "shed": 5}}
    # disabled / under-K: pass-through
    assert cap_tenant_rows(rows, 0) == rows
    assert cap_tenant_rows(rows, 10) == rows


# -- DRR weighted fair share -------------------------------------------------


def _drr_items(n_per_tenant, cost=256):
    # interleaved arrival: b0, s0, p0, b1, s1, p1, ...
    out = []
    for i in range(n_per_tenant):
        for t in ("hobby", "corp", "acme"):
            out.append((t, cost, i))
    return out


_DRR_WEIGHT = {"hobby": 1.0, "corp": 4.0, "acme": 16.0}


def test_drr_weighted_share_convergence():
    drr = DrrScheduler(quantum=256)
    items = _drr_items(20)
    out = drr.order(items, tenant_of=lambda it: it[0],
                    cost_of=lambda it: it[1],
                    weight_of=lambda it: _DRR_WEIGHT[it[0]])
    assert sorted(map(id, out)) == sorted(map(id, items))  # a permutation
    # the first visit round serves items proportional to weight: 1 hobby,
    # 4 corp, 16 acme of the first 21 served
    head = out[:21]
    counts = {t: sum(1 for it in head if it[0] == t)
              for t in ("hobby", "corp", "acme")}
    assert counts == {"hobby": 1, "corp": 4, "acme": 16}, counts
    # FIFO within each tenant is preserved
    for t in ("hobby", "corp", "acme"):
        seqs = [it[2] for it in out if it[0] == t]
        assert seqs == sorted(seqs)


def test_drr_single_tenant_exact_fifo():
    drr = DrrScheduler(quantum=1)  # tiny quantum must not matter
    items = [("only", 999, i) for i in range(10)]
    assert drr.order(items, tenant_of=lambda it: it[0],
                     cost_of=lambda it: it[1],
                     weight_of=lambda it: 1.0) == items


def test_drr_deficit_resets_when_queue_empties():
    drr = DrrScheduler(quantum=256)
    items = [("a", 256, 0), ("b", 256, 0)]
    drr.order(items, tenant_of=lambda it: it[0],
              cost_of=lambda it: it[1], weight_of=lambda it: 16.0)
    # both queues drained inside the round: no banked credit while idle
    assert drr._deficit.get("a", 0.0) == 0.0
    assert drr._deficit.get("b", 0.0) == 0.0
    drr.forget("a")  # idempotent on absent tenants
    drr.forget("never-seen")


# -- shed-cause envelope markers ---------------------------------------------


def test_shed_cause_token_roundtrip():
    msg = f"displaced by weighted fair share ({shed_cause('fair_share')}); retry"
    assert shed_cause_of(msg) == "fair_share"
    assert error_is_retryable(msg)  # the token alone marks it retryable
    assert shed_cause_of({"error": "queue full (shed_cause=depth)"}) == "depth"
    # absent or unrecognized causes read as generic overload (old workers)
    assert shed_cause_of("overloaded: retry on another worker") is None
    assert shed_cause_of("boom (shed_cause=gremlins)") is None
    assert shed_cause_of(None) is None


def test_gateway_envelope_error_mapping():
    # quota / fair_share sheds are the client's fault -> typed 429 with
    # Retry-After; infrastructure sheds stay 503
    status, body, extra = _envelope_error_response(
        "monthly quota exhausted (shed_cause=quota)")
    assert status == 429 and body["error"]["type"] == "rate_limit_error"
    assert body["error"]["cause"] == "quota"
    assert extra == {"Retry-After": "1"}
    status, body, extra = _envelope_error_response(
        "displaced by weighted fair share (shed_cause=fair_share); retry")
    assert status == 429 and body["error"]["cause"] == "fair_share"
    status, body, extra = _envelope_error_response(
        "brownout: batch class shed first (shed_cause=brownout); retry "
        "on another worker")
    assert status == 503 and body["error"]["cause"] == "brownout"
    assert extra == {"Retry-After": "1"}


def test_tenant_stats_rollup():
    ts = TenantStats()
    for i in range(4):
        ts.record_request(f"t{i}")
    ts.record_served("t0", tokens=8, queue_age_ms=2.0)
    ts.record_shed("t1")
    ts.record_preempted("t2")
    snap = ts.snapshot()
    assert snap["t0"]["served"] == 1 and snap["t0"]["tokens"] == 8
    assert snap["t1"]["shed"] == 1 and snap["t2"]["preempted"] == 1
    capped = ts.snapshot(top_k=2)
    assert "other" in capped and len(capped) == 3


# -- batcher policy: brownout by class, preemption, anonymous backcompat -----


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig.tiny(n_layers=2, max_seq_len=128)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompt(n, mul=7, add=3, vocab=509):
    return [(i * mul + add) % vocab for i in range(n)]


_QOS_KW = dict(max_slots=2, max_seq_len=64, buckets=[8, 64],
               prefill_chunk=32, kv_block_tokens=32, kv_pool_blocks=3,
               decode_burst=1, admit_coalesce_ms=0.0, paged=True,
               qos_preempt=True)


@async_test
async def test_brownout_sheds_batch_before_standard(model):
    """BROWNOUT is the lowest class still admitted: batch bounces with the
    cause-tagged retryable shed while standard and premium serve."""
    cfg, params = model
    b = ContinuousBatcher(params, cfg, max_slots=2, max_seq_len=64,
                          buckets=[8, 64], max_queue=8,
                          brownout=BrownoutConfig())
    try:
        b.brownout.level = BROWNOUT
        sp = SamplingParams(temperature=0.0, max_tokens=2)
        with pytest.raises(BatcherOverloaded) as ei:
            async for _ in b.submit([1, 2], sp, tenant="hobby",
                                    priority="batch"):
                pass
        assert shed_cause_of(str(ei.value)) == "brownout"
        assert error_is_retryable(str(ei.value))
        out = [t async for t in b.submit([1, 2], sp, tenant="corp",
                                         priority="standard")]
        assert len(out) == 2
        out = [t async for t in b.submit([1, 2], sp, tenant="acme",
                                         priority="premium")]
        assert len(out) == 2
        snap = b.tenant_stats.snapshot()
        assert snap["hobby"]["shed"] == 1 and snap["hobby"]["served"] == 0
        assert snap["corp"]["served"] == 1 and snap["acme"]["served"] == 1
    finally:
        b.stop()


@async_test
async def test_shed_only_spares_premium(model):
    """At SHED_ONLY standard bounces too (the pre-QoS default-class
    behavior), but premium still rides through the gate."""
    cfg, params = model
    b = ContinuousBatcher(params, cfg, max_slots=2, max_seq_len=64,
                          buckets=[8, 64], max_queue=8,
                          brownout=BrownoutConfig())
    try:
        b.brownout.level = SHED_ONLY
        sp = SamplingParams(temperature=0.0, max_tokens=2)
        with pytest.raises(BatcherOverloaded) as ei:
            async for _ in b.submit([1, 2], sp):  # anonymous -> standard
                pass
        assert "brownout shed-only" in str(ei.value)
        assert shed_cause_of(str(ei.value)) == "brownout"
        b.brownout.level = SHED_ONLY  # re-force (serving may have ticked it)
        out = [t async for t in b.submit([1, 2], sp, tenant="acme",
                                         priority="premium")]
        assert len(out) == 2
    finally:
        b.stop()


async def _pressure_pair(b, pa, pb, na, nb, qa, qb):
    """A (tenant/priority ``qa``) decodes first; once 2 of A's tokens
    arrived, B (``qb``) submits — whose admit exhausts the 3-block pool.
    The owner thread holds A's fourth decode step until B is in its inbox
    (``hold_decodes_until_queued``): A cannot run out its 12 tokens before B
    has arrived, however late this loop gets to submit it.
    Returns (a_tokens, b_tokens)."""
    hold_decodes_until_queued(b)
    spa = SamplingParams(temperature=0.0, max_tokens=na)
    spb = SamplingParams(temperature=0.0, max_tokens=nb)
    started = asyncio.get_running_loop().create_future()

    async def run_a():
        out = []
        async for t in b.submit(pa, spa, tenant=qa[0], priority=qa[1]):
            out.append(t)
            if len(out) == 2 and not started.done():
                started.set_result(None)
        return out

    async def run_b():
        return [t async for t in b.submit(pb, spb, tenant=qb[0],
                                          priority=qb[1])]

    ta = asyncio.ensure_future(run_a())
    await started
    tb = asyncio.ensure_future(run_b())
    return await ta, await tb


@async_test
async def test_premium_preempts_batch_bit_identical(model):
    """A premium admit on a full pool preempts the batch slot to the host
    tier (reason ``preempted``, counted per tenant) instead of shedding
    anyone; the victim resumes and finishes bit-identically with the
    ample-pool greedy sequence."""
    cfg, params = model
    pa, pb = _prompt(33), _prompt(40, mul=11, add=5)
    ample = ContinuousBatcher(params, cfg, **{**_QOS_KW,
                                              "kv_pool_blocks": 0})
    try:
        sp = SamplingParams(temperature=0.0, max_tokens=12)
        want_a = [t async for t in ample.submit(pa, sp)]
        spb = SamplingParams(temperature=0.0, max_tokens=8)
        want_b = [t async for t in ample.submit(pb, spb)]
    finally:
        ample.stop()
    b = ContinuousBatcher(params, cfg, **_QOS_KW)
    try:
        got_a, got_b = await _pressure_pair(
            b, pa, pb, 12, 8, ("hobby", "batch"), ("acme", "premium"))
        assert got_a == want_a, "preempted slot did not resume bit-identically"
        assert got_b == want_b
        assert b._suspend_stats["suspended_total"] >= 1
        assert b._suspend_stats["resumed_total"] >= 1
        snap = b.tenant_stats.snapshot()
        # the victim was parked, not shed — preemption is its own counter
        assert snap["hobby"]["preempted"] >= 1
        assert snap["hobby"]["shed"] == 0 and snap["acme"]["shed"] == 0
        assert snap["hobby"]["served"] == 1 and snap["acme"]["served"] == 1
        assert b.stats.shed_cause_counts().get("kv_pool", 0) == 0
    finally:
        b.stop()


@async_test
async def test_tenantless_submit_is_anonymous_standard(model):
    """The raw-NATS backcompat contract at the batcher seam: a submit
    without tenant/priority serves exactly as before under the anonymous
    standard identity."""
    cfg, params = model
    b = ContinuousBatcher(params, cfg, max_slots=2, max_seq_len=64,
                          buckets=[8, 64])
    try:
        sp = SamplingParams(temperature=0.0, max_tokens=3)
        out = [t async for t in b.submit([5, 6, 7], sp)]
        assert len(out) == 3
        snap = b.tenant_stats.snapshot()
        assert set(snap) == {ANON_TENANT}
        assert snap[ANON_TENANT]["requests"] == 1
        assert snap[ANON_TENANT]["served"] == 1
        assert snap[ANON_TENANT]["tokens"] == 3
    finally:
        b.stop()


@async_test
async def test_worker_renders_per_tenant_families(model):
    """The worker exposition carries the lmstudio_tenant_* families under
    the capped ``tenant`` label for every loaded engine."""
    from nats_llm_studio_tpu.config import WorkerConfig
    from nats_llm_studio_tpu.serve.worker import Worker

    cfg, params = model
    b = ContinuousBatcher(params, cfg, max_slots=2, max_seq_len=64,
                          buckets=[8, 64])
    try:
        sp = SamplingParams(temperature=0.0, max_tokens=2)
        out = [t async for t in b.submit([1, 2], sp, tenant="acme",
                                         priority="premium")]
        assert len(out) == 2

        class _Eng:
            batcher = b

        class _Reg:
            def stats(self):
                return {}

            def loaded_engines(self):
                return {"acme/q": _Eng()}

        w = Worker(WorkerConfig(), _Reg())
        wid = w.worker_id
        text = w.render_prometheus()
        assert (f'\nlmstudio_tenant_requests_total'
                f'{{model="acme/q",tenant="acme",worker_id="{wid}"}} 1\n') in text
        assert (f'\nlmstudio_tenant_served_total'
                f'{{model="acme/q",tenant="acme",worker_id="{wid}"}} 1\n') in text
        assert (f'\nlmstudio_tenant_tokens_total'
                f'{{model="acme/q",tenant="acme",worker_id="{wid}"}} 2\n') in text
        assert (f'\nlmstudio_tenant_shed_total'
                f'{{model="acme/q",tenant="acme",worker_id="{wid}"}} 0\n') in text
        assert (f'\nlmstudio_tenant_preempted_total'
                f'{{model="acme/q",tenant="acme",worker_id="{wid}"}} 0\n') in text
        assert (f'lmstudio_tenant_queue_age_ms_total'
                f'{{model="acme/q",tenant="acme"') in text
    finally:
        b.stop()


# -- aggregator: post-merge tenant cardinality cap ---------------------------


def test_aggregator_caps_tenant_cardinality_after_merge():
    """Disjoint per-worker top-Ks union past K cluster-wide: the merge
    re-applies the cap so the cluster view stays at top-K + ``other``."""
    texts = []
    for w, base in (("w1", 0), ("w2", 6)):
        r = PromRenderer(default_labels={"worker_id": w})
        for i in range(6):
            r.counter("lmstudio_tenant_served_total", i + 1,
                      labels={"model": "m", "tenant": f"t{base + i}"})
        texts.append(r.render())
    out = PromRenderer()
    merge_into(out, texts, tenant_topk=3)
    text = out.render()
    # 12 distinct tenants in -> 3 named + "other" out, totals preserved
    assert text.count('tenant="') == 4
    assert 'lmstudio_tenant_served_total{model="m",tenant="other"} 25' in text
    # under the cap nothing rolls up
    out2 = PromRenderer()
    merge_into(out2, texts, tenant_topk=16)
    text2 = out2.render()
    assert text2.count('tenant="') == 12 and 'tenant="other"' not in text2


# -- gateway front door: auth, rate, quota, header stamping ------------------


class RecordingEngine(EchoEngine):
    """Echo engine that records every chat payload the worker hands it,
    so tests can see what crossed the bus (tenant/priority stamping)."""

    def __init__(self, model_id):
        super().__init__(model_id)
        self.payloads = []

    async def chat(self, payload):
        self.payloads.append(dict(payload))
        return await super().chat(payload)


class RecordingRegistry(FakeRegistry):
    def __init__(self):
        super().__init__()
        self.engine = RecordingEngine("fake-echo-1")
        self.engines = {"fake-echo-1": self.engine}


@async_test
async def test_gateway_requires_key_when_configured():
    async with GatewayHarness(api_keys="sk-a:acme:premium:2.5") as h:
        status, _, body = await h.request("POST", "/v1/chat/completions", CHAT)
        assert status == 401
        assert body["error"]["type"] == "authentication_error"
        assert body["error"]["code"] == "invalid_api_key"
        status, _, body = await h.request(
            "POST", "/v1/chat/completions", CHAT,
            headers={"Authorization": "Bearer sk-wrong"})
        assert status == 401 and body["error"]["code"] == "invalid_api_key"
        # /v1/models is gated on key validity too (no rate tokens spent)
        status, _, _ = await h.request("GET", "/v1/models")
        assert status == 401
        status, _, _ = await h.request(
            "GET", "/v1/models", headers={"Authorization": "Bearer sk-a"})
        assert status == 200
        # refusals show under the rejected family as tenant="unknown"
        text = h.gw.render_prometheus()
        assert 'lmstudio_gateway_tenant_rejected_total' in text
        assert 'tenant="unknown"' in text


@async_test
async def test_gateway_stamps_resolved_tenant_onto_bus():
    """The worker sees the tenant/class the KEY resolves to — never a
    client-claimed header — and the reply charges the tenant's usage."""
    reg = RecordingRegistry()
    async with GatewayHarness(registries=[reg],
                              api_keys="sk-a:acme:premium:2.5") as h:
        status, _, body = await h.request(
            "POST", "/v1/chat/completions", CHAT,
            headers={"Authorization": "Bearer sk-a",
                     # spoof attempts must be ignored in favor of the key
                     "X-Tenant": "victim", "X-Priority": "batch"})
        assert status == 200
        assert body["choices"][0]["message"]["content"].startswith("echo:")
        p = reg.engine.payloads[-1]
        assert p["_tenant"] == "acme"
        assert p["_priority"] == "premium:2.5"
        text = h.gw.render_prometheus()
        assert 'lmstudio_gateway_tenant_requests_total{' in text
        assert 'tenant="acme"' in text
        # completion usage booked against the tenant's month
        assert h.gw._usage.tokens_used("acme") == body["usage"]["completion_tokens"]


@async_test
async def test_gateway_rate_limit_429_with_retry_after():
    # rps=0.5 -> burst 1: the second request inside the window must 429
    async with GatewayHarness(api_keys="sk-r:acme:standard:0:0.5") as h:
        hdr = {"Authorization": "Bearer sk-r"}
        status, _, _ = await h.request("POST", "/v1/chat/completions", CHAT,
                                       headers=hdr)
        assert status == 200
        status, headers, body = await h.request(
            "POST", "/v1/chat/completions", CHAT, headers=hdr)
        assert status == 429
        assert body["error"]["code"] == "rate_limit_exceeded"
        assert body["error"]["cause"] == "quota"
        assert int(headers["retry-after"]) >= 1


@async_test
async def test_gateway_monthly_quota_429():
    # quota of 1 completion token: the first echo reply (3 words) burns it
    async with GatewayHarness(api_keys="sk-q:acme:standard:0:0:1") as h:
        hdr = {"Authorization": "Bearer sk-q"}
        status, _, _ = await h.request("POST", "/v1/chat/completions", CHAT,
                                       headers=hdr)
        assert status == 200
        status, headers, body = await h.request(
            "POST", "/v1/chat/completions", CHAT, headers=hdr)
        assert status == 429
        assert body["error"]["code"] == "insufficient_quota"
        assert body["error"]["cause"] == "quota"
        assert headers["retry-after"] == "3600"


@async_test
async def test_gateway_without_keys_serves_unauthenticated():
    """No API_KEYS configured == the pre-QoS deployment: every caller is
    the anonymous standard tenant, nothing is stamped on the bus."""
    reg = RecordingRegistry()
    async with GatewayHarness(registries=[reg]) as h:
        status, _, body = await h.request("POST", "/v1/chat/completions", CHAT)
        assert status == 200
        assert body["choices"][0]["message"]["content"].startswith("echo:")
        p = reg.engine.payloads[-1]
        assert "_tenant" not in p and "_priority" not in p
