"""Multi-worker cluster tests (ISSUE 10): advert flow into the router's
member table, load/locality steering, the ``X-Excluded-Workers`` bounce
round-trip, shed-retried-onto-the-peer failover over the real queue group,
graceful drain handoff, and deadline-budget-capped retries."""

import asyncio
import json
import time

import pytest

from nats_llm_studio_tpu.config import WorkerConfig
from nats_llm_studio_tpu.serve import ClusterRouter, Worker, prompt_head_hash
from nats_llm_studio_tpu.serve.api import EngineError
from nats_llm_studio_tpu.serve.router import RecentHeads, RouterProcess
from nats_llm_studio_tpu.transport import EmbeddedBroker, RetryPolicy, connect
from nats_llm_studio_tpu.transport import protocol as p
from nats_llm_studio_tpu.transport.envelope import deadline_header_value

from conftest import async_test
from fakes import FakeRegistry


class SheddingRegistry(FakeRegistry):
    """Sheds the first ``shed_times`` chats with the retryable overload
    envelope, then serves — the worker-side behavior a retry must survive."""

    def __init__(self, *args, shed_times: int = 10**9, **kwargs):
        super().__init__(*args, **kwargs)
        self.shed_times = shed_times
        self.sheds = 0

    async def get_engine(self, model_id):
        if self.sheds < self.shed_times:
            self.sheds += 1
            raise EngineError("overloaded: test shed, retry on another worker")
        return await super().get_engine(model_id)


class ClusterHarness:
    """N workers (fast adverts) + one client on an embedded broker."""

    def __init__(self, n_workers=2, registries=None, advert_interval_s=0.05,
                 roles=None):
        self.n_workers = n_workers
        self.registries = registries
        self.advert_interval_s = advert_interval_s
        self.roles = roles  # optional per-worker WORKER_ROLE list

    async def __aenter__(self):
        self.broker = await EmbeddedBroker().start()
        if self.registries is None:
            self.registries = [FakeRegistry() for _ in range(self.n_workers)]
        self.workers = []
        for i, reg in enumerate(self.registries):
            w = Worker(
                WorkerConfig(
                    nats_url=self.broker.url,
                    cluster_advert_interval_s=self.advert_interval_s,
                    worker_role=(self.roles[i] if self.roles else ""),
                ),
                reg,
            )
            await w.start()
            self.workers.append(w)
        self.nc = await connect(self.broker.url)
        return self

    async def __aexit__(self, *exc):
        await self.nc.close()
        for w in self.workers:
            await w.drain()
        await self.broker.stop()

    @staticmethod
    def chat(content="hi", model="fake-echo-1"):
        return {"model": model, "messages": [{"role": "user", "content": content}]}

    async def req(self, op, payload, timeout=5.0, headers=None, retry=None):
        body = payload if isinstance(payload, bytes) else json.dumps(payload).encode()
        msg = await self.nc.request(
            f"lmstudio.{op}", body, timeout=timeout, headers=headers, retry=retry
        )
        return json.loads(msg.payload), msg


# -- pure units --------------------------------------------------------------


def test_prompt_head_hash_is_length_delimited_and_budget_capped():
    # message boundaries can't collide: ("ab","c") vs ("a","bc")
    a = prompt_head_hash("m", [{"role": "u", "content": "ab"}, {"role": "u", "content": "c"}])
    b = prompt_head_hash("m", [{"role": "u", "content": "a"}, {"role": "u", "content": "bc"}])
    assert a != b
    # the model is part of the key (different vocab -> different token ids)
    msgs = [{"role": "user", "content": "hello"}]
    assert prompt_head_hash("m1", msgs) != prompt_head_hash("m2", msgs)
    # only the first `chars` characters count: equal heads hash equal
    long_a = [{"role": "user", "content": "abcd" + "X" * 50}]
    long_b = [{"role": "user", "content": "abcd" + "Y" * 50}]
    assert prompt_head_hash("m", long_a, chars=4) == prompt_head_hash("m", long_b, chars=4)
    assert prompt_head_hash("m", long_a, chars=8) != prompt_head_hash("m", long_b, chars=8)
    # malformed messages degrade to a model-only hash, never raise
    assert prompt_head_hash("m", None) == prompt_head_hash("m", "not-a-list")


def test_recent_heads_lru_eviction_and_refresh():
    lru = RecentHeads(capacity=2)
    lru.add("a")
    lru.add("b")
    lru.add("a")  # refresh: "b" is now oldest
    lru.add("c")
    assert lru.snapshot() == ["a", "c"]


def test_router_pick_ranking_staleness_and_mark_dead():
    r = ClusterRouter(None, stale_after_s=5.0)
    msgs = [{"role": "user", "content": "the shared prompt head"}]
    head = prompt_head_hash("m", msgs)

    # draining and excluded workers never win
    r.ingest({"worker_id": "w-a", "queue_depth": 0, "draining": True})
    r.ingest({"worker_id": "w-b", "queue_depth": 9})
    assert r.pick(model="m", messages=msgs) == "w-b"
    assert r.pick(model="m", messages=msgs, excluded=["w-b"]) is None

    # lower brownout beats lower depth; model-loaded beats depth
    r.ingest({"worker_id": "w-a", "queue_depth": 0, "brownout": 1, "draining": False})
    assert r.pick(model="m", messages=msgs) == "w-b"
    r.ingest({"worker_id": "w-a", "queue_depth": 0, "brownout": 0})
    r.ingest({"worker_id": "w-b", "queue_depth": 9, "models": ["m"]})
    assert r.pick(model="m", messages=msgs) == "w-b"

    # prefix-head locality wins outright — unless the sticky worker is
    # SHED_ONLY (brownout 2), where steering extra load at it is harmful
    r.ingest({"worker_id": "w-a", "queue_depth": 0, "models": ["m"], "heads": [head]})
    assert r.pick(model="m", messages=msgs) == "w-a"
    assert r.stats.locality_total == 1
    r.ingest({"worker_id": "w-a", "queue_depth": 0, "models": ["m"], "heads": [head],
              "brownout": 2})
    assert r.pick(model="m", messages=msgs) == "w-b"

    # out-of-order adverts are dropped by seq
    r.ingest({"worker_id": "w-b", "queue_depth": 1, "models": ["m"], "seq": 10})
    r.ingest({"worker_id": "w-b", "queue_depth": 99, "models": [], "seq": 9})
    assert r._members["w-b"].queue_depth == 1

    # ...but a respawned worker reusing the id (its seq restarted near
    # zero) must not be ignored until the stale window ages the ghost out
    # (ISSUE 15): seq <= SEQ_RESTART_MAX is accepted as a restart
    r.ingest({"worker_id": "w-b", "queue_depth": 3, "models": ["m"], "seq": 2})
    assert r._members["w-b"].queue_depth == 3
    # as is a backward jump beyond the reorder window; a small backward
    # step inside it is still just a late packet
    r.ingest({"worker_id": "w-b", "queue_depth": 1, "models": ["m"], "seq": 500})
    r.ingest({"worker_id": "w-b", "queue_depth": 99, "models": ["m"], "seq": 460})
    assert r._members["w-b"].queue_depth == 1  # within window: stale, dropped
    r.ingest({"worker_id": "w-b", "queue_depth": 7, "models": ["m"], "seq": 100})
    assert r._members["w-b"].queue_depth == 7  # beyond window: a restart

    # mark_dead drops the member NOW
    r.mark_dead("w-b")
    assert r.pick(model="m", messages=msgs) == "w-a"
    assert r.stats.dead_marked_total == 1

    # stale members fall out of the live view
    r2 = ClusterRouter(None, stale_after_s=0.05)
    r2.ingest({"worker_id": "w-z"})
    assert [m.worker_id for m in r2.members()] == ["w-z"]
    time.sleep(0.1)
    assert r2.members() == []
    assert r2.pick(model="m", messages=msgs) is None


def test_router_pick_pair_role_routing():
    """Role-aware pick_pair (ISSUE 13): prefill-role workers are held out
    of serving whenever any other worker is live, decode-role winners get
    paired with the best prefill peer (the two-hop), and everything
    degrades to monolithic picks when the topology loses a role."""
    r = ClusterRouter(None, stale_after_s=5.0)

    # roleless cluster: plain pick, never a prefill peer
    r.ingest({"worker_id": "w-a", "queue_depth": 0})
    assert r.pick_pair(model="m") == ("w-a", None)

    # prefill-role workers don't serve chats while any other worker is live
    r.ingest({"worker_id": "w-p", "queue_depth": 0, "role": "prefill"})
    assert r.pick_pair(model="m")[0] == "w-a"

    # a decode-role winner is paired with the best prefill peer
    r.ingest({"worker_id": "w-d", "queue_depth": 5, "role": "decode",
              "models": ["m"]})
    assert r.pick_pair(model="m") == ("w-d", "w-p")
    assert r.stats.two_hop_total == 1
    assert r.pick(model="m") == "w-d"  # pick() delegates to pick_pair()

    # a SHED_ONLY prefill peer is not worth the hop
    r.ingest({"worker_id": "w-p", "queue_depth": 0, "role": "prefill",
              "brownout": 2})
    assert r.pick_pair(model="m") == ("w-d", None)
    r.ingest({"worker_id": "w-p", "queue_depth": 0, "role": "prefill"})

    # a monolithic winner never hops
    r.ingest({"worker_id": "w-a", "queue_depth": 0, "models": ["m"]})
    assert r.pick_pair(model="m") == ("w-a", None)

    # only prefill-role workers left: they serve monolithically (degrade)
    r.mark_dead("w-a")
    r.mark_dead("w-d")
    assert r.pick_pair(model="m") == ("w-p", None)
    # exclusion applies to the serving end as usual
    assert r.pick_pair(model="m", excluded=["w-p"]) == (None, None)


# -- adverts + steering over the real broker ---------------------------------


@async_test
async def test_worker_adverts_populate_router_and_steer():
    async with ClusterHarness(n_workers=2) as h:
        router = await ClusterRouter(h.nc).start()
        try:
            deadline = time.monotonic() + 5.0
            while len(router.members()) < 2 and time.monotonic() < deadline:
                await asyncio.sleep(0.02)
            ids = sorted(m.worker_id for m in router.members())
            assert ids == sorted(w.worker_id for w in h.workers)
            for m in router.members():
                assert m.models == ("fake-echo-1",)
                assert m.draining is False

            msg = await router.request_chat(h.chat(), timeout=5.0)
            resp = json.loads(msg.payload)
            assert resp["ok"] is True
            assert (msg.headers or {}).get(p.WORKER_HEADER) in ids
            assert router.stats.routed_total == 1
            assert router.stats.fallback_total == 0
        finally:
            await router.stop()

        # a router with an empty member table degrades to the queue group —
        # attaching one is always safe
        cold = ClusterRouter(h.nc)  # never started: no adverts ingested
        msg = await cold.request_chat(h.chat(), timeout=5.0)
        assert json.loads(msg.payload)["ok"] is True
        assert cold.stats.fallback_total == 1
        assert cold.stats.routed_total == 0


@async_test
async def test_role_cluster_degrades_gracefully_without_kv_engines():
    """A prefill+decode topology over engines that can't export/import KV
    (fakes.EchoEngine has no import_prefix hook) still serves every chat:
    the router two-hops to the decode worker, which skips the pull without
    counting a transfer failure."""
    async with ClusterHarness(n_workers=2, roles=["prefill", "decode"]) as h:
        router = await ClusterRouter(h.nc).start()
        try:
            deadline = time.monotonic() + 5.0
            while len(router.members()) < 2 and time.monotonic() < deadline:
                await asyncio.sleep(0.02)
            roles = {m.worker_id: m.role for m in router.members()}
            assert sorted(roles.values()) == ["decode", "prefill"]
            decode_wid = next(w for w, role in roles.items() if role == "decode")

            msg = await router.request_chat(h.chat(), timeout=5.0)
            assert json.loads(msg.payload)["ok"] is True
            assert (msg.headers or {}).get(p.WORKER_HEADER) == decode_wid
            assert router.stats.two_hop_total == 1
            wd = next(w for w in h.workers if w.worker_id == decode_wid)
            assert wd._kv_transfer_failures == 0
        finally:
            await router.stop()


@async_test
async def test_directed_subjects_and_excluded_bounce_envelope():
    async with ClusterHarness(n_workers=1) as h:
        w = h.workers[0]
        wid = w.worker_id

        # directed health: draining state per worker, not queue-group roulette
        resp, _ = await h.req(f"worker.{wid}.health", {})
        assert resp["ok"] is True
        assert resp["data"]["worker_id"] == wid
        assert resp["data"]["draining"] is False

        # a chat naming this worker in X-Excluded-Workers bounces retryably
        # with the one-shot excluded_bounce marker — it never serves
        resp, msg = await h.req(
            f"worker.{wid}.chat_model", h.chat(),
            headers={p.EXCLUDED_WORKERS_HEADER: wid},
        )
        assert resp["ok"] is False
        assert resp["retryable"] is True
        assert "retry on another worker" in resp["error"]
        assert resp["data"]["excluded_bounce"] is True
        assert resp["data"]["worker_id"] == wid
        assert (msg.headers or {}).get(p.WORKER_HEADER) == wid
        assert w._excluded_bounce_total == 1


@async_test
async def test_excluded_bounce_roundtrips_through_client_retry():
    """Shed -> exclude -> redelivery bounces -> exclusion consumed -> served.
    A single-worker group must stay servable after one shed (the bounce is a
    one-shot deflection, not a permanent blacklist)."""
    reg = SheddingRegistry(shed_times=1)
    async with ClusterHarness(n_workers=1, registries=[reg]) as h:
        resp, msg = await h.req(
            "chat_model", h.chat(),
            retry=RetryPolicy(max_attempts=5, backoff_s=0.01, jitter=0.0),
        )
        assert resp["ok"] is True
        assert reg.sheds == 1
        # attempt 2 landed back on the only worker, which self-checked the
        # header and bounced instead of serving
        assert h.workers[0]._excluded_bounce_total == 1
        assert (msg.headers or {}).get(p.WORKER_HEADER) == h.workers[0].worker_id


@async_test
async def test_shed_by_one_worker_is_retried_onto_the_other():
    shedder = SheddingRegistry()  # sheds every chat, forever
    healthy = FakeRegistry()
    async with ClusterHarness(n_workers=2, registries=[shedder, healthy]) as h:
        resp, msg = await h.req(
            "chat_model", h.chat(),
            timeout=10.0,
            retry=RetryPolicy(max_attempts=12, backoff_s=0.01, jitter=0.0),
        )
        assert resp["ok"] is True
        assert (msg.headers or {}).get(p.WORKER_HEADER) == h.workers[1].worker_id
        # the healthy worker was never named in an exclusion header
        assert h.workers[1]._excluded_bounce_total == 0


@async_test
async def test_router_steers_retry_away_from_shedding_worker():
    """Steered failover is deterministic: the shed adds the worker to the
    exclusion list AND the pick filter, so the retry goes straight to the
    peer — no queue-group roulette, no redelivery bounce."""
    shedder = SheddingRegistry()
    healthy = FakeRegistry()
    async with ClusterHarness(n_workers=2, registries=[shedder, healthy]) as h:
        wid_shed = h.workers[0].worker_id
        wid_ok = h.workers[1].worker_id
        router = ClusterRouter(h.nc)  # not started: member table is injected
        router.ingest({"worker_id": wid_shed, "queue_depth": 0, "models": ["fake-echo-1"]})
        router.ingest({"worker_id": wid_ok, "queue_depth": 5, "models": ["fake-echo-1"]})
        assert router.pick(model="fake-echo-1") == wid_shed  # least loaded

        msg = await router.request_chat(
            h.chat(), timeout=5.0,
            retry=RetryPolicy(max_attempts=3, backoff_s=0.01, jitter=0.0),
        )
        resp = json.loads(msg.payload)
        assert resp["ok"] is True
        assert (msg.headers or {}).get(p.WORKER_HEADER) == wid_ok
        assert shedder.sheds == 1
        assert router.stats.routed_total == 2
        # directed steering honors the exclusion — the shedder never saw the
        # retry, so its self-check counter stayed at zero
        assert h.workers[0]._excluded_bounce_total == 0


@async_test
async def test_router_process_forwards_route_subject():
    async with ClusterHarness(n_workers=2) as h:
        proc = RouterProcess(h.nc, retry=RetryPolicy(max_attempts=3, backoff_s=0.01))
        await proc.start()
        try:
            deadline = time.monotonic() + 5.0
            while len(proc.router.members()) < 2 and time.monotonic() < deadline:
                await asyncio.sleep(0.02)
            msg = await h.nc.request(
                "lmstudio.route.chat_model",
                json.dumps(h.chat("via router")).encode(),
                timeout=5.0,
            )
            resp = json.loads(msg.payload)
            assert resp["ok"] is True
            text = resp["data"]["response"]["choices"][0]["message"]["content"]
            assert text == "echo: via router"
            # the reply is relayed verbatim, serving worker header included
            wid = (msg.headers or {}).get(p.WORKER_HEADER)
            assert wid in {w.worker_id for w in h.workers}
        finally:
            await proc.stop()


# -- graceful drain handoff --------------------------------------------------


@async_test
async def test_admin_drain_hands_off_to_peer():
    async with ClusterHarness(n_workers=2) as h:
        wa, wb = h.workers
        resp, _ = await h.req("admin.drain", {"worker_id": wa.worker_id})
        assert resp["ok"] is True
        assert resp["data"]["worker_id"] == wa.worker_id
        assert resp["data"]["draining"] is True
        assert resp["data"]["finished_in_time"] is True
        assert wa.draining is True and wb.draining is False

        # the drained worker left the queue group before replying, so every
        # new queue-group request lands on the peer — no retries needed
        for i in range(10):
            resp, msg = await h.req("chat_model", h.chat(f"r{i}"))
            assert resp["ok"] is True
            assert (msg.headers or {}).get(p.WORKER_HEADER) == wb.worker_id

        # directed chat at the drained worker bounces retryably
        resp, _ = await h.req(f"worker.{wa.worker_id}.chat_model", h.chat())
        assert resp["ok"] is False and resp["retryable"] is True
        assert "worker draining" in resp["error"]
        assert wa._drain_bounce_total == 1

        # directed health and the advert both surface the drain state
        resp, _ = await h.req(f"worker.{wa.worker_id}.health", {})
        assert resp["data"]["status"] == "draining"
        assert resp["data"]["draining"] is True
        assert wa.build_advert()["draining"] is True

        # drain is idempotent
        resp, _ = await h.req("admin.drain", {"worker_id": wa.worker_id})
        assert resp["data"].get("already_draining") is True

        # a drain addressed to nobody gets no reply (peers stay silent so
        # the addressee's reply is THE reply) — the requester times out
        with pytest.raises(asyncio.TimeoutError):
            await h.req("admin.drain", {"worker_id": "w-nonexistent"}, timeout=0.3)

        # validation still replies
        resp, _ = await h.req("admin.drain", {})
        assert resp["ok"] is False and "worker_id" in resp["error"]


# -- deadline budget caps retries (satellite a) ------------------------------


@async_test
async def test_retry_stops_when_deadline_budget_exhausted():
    reg = SheddingRegistry()  # never serves: every attempt is a retryable shed
    async with ClusterHarness(n_workers=1, registries=[reg]) as h:
        t0 = time.monotonic()
        resp, _ = await h.req(
            "chat_model", h.chat(),
            timeout=0.6,
            retry=RetryPolicy(
                max_attempts=50, backoff_s=0.25, max_backoff_s=0.25,
                jitter=0.0,
            ),
        )
        elapsed = time.monotonic() - t0
        # the last retryable envelope is returned honestly once the budget
        # can't fund another backoff — NOT 50 attempts x 0.25s of spin
        assert resp["ok"] is False
        assert resp["retryable"] is True
        assert elapsed < 3.0
        assert reg.sheds + h.workers[0]._excluded_bounce_total <= 5


@async_test
async def test_a_worker_killed_mid_wave_costs_no_request_and_the_survivor_counts_the_hops():
    """One of two workers loses its connection, with no drain and no goodbye,
    while a wave is in flight: every request is served inside its budget (an
    attempt stuck on the dead worker times out and hops), and the survivor's
    own exposition, scraped on its directed subject, accounts for what it
    served and carries the bounce counter a rerouted hop would land in."""
    async with ClusterHarness(n_workers=2) as h:
        survivor = h.workers[1]

        async def one(i):
            # asyncio.TimeoutError out of gather() would be a budget that ran out
            resp, _ = await h.req(
                "chat_model", h.chat(f"r{i}"), timeout=1.0,
                headers={p.DEADLINE_HEADER: deadline_header_value(20.0)},
                retry=RetryPolicy(max_attempts=40, backoff_s=0.05, jitter=0.0,
                                  retry_on_timeout=True),
            )
            return resp

        wave = [asyncio.ensure_future(one(i)) for i in range(12)]
        await asyncio.sleep(0)
        await h.workers[0].nc.close()   # the kill
        results = await asyncio.gather(*wave)
        assert all(r["ok"] for r in results), results
        more = await asyncio.gather(*[one(i) for i in range(12, 16)])
        assert all(r["ok"] for r in more), more

        msg = await h.nc.request(
            f"lmstudio.worker.{survivor.worker_id}.metrics.prom", b"", timeout=5.0)
        rows = {line.split("{")[0].split()[0]: float(line.rsplit(None, 1)[1])
                for line in msg.payload.decode().splitlines() if not line.startswith("#")}
        assert rows["lmstudio_requests_total"] >= 4
        assert rows["lmstudio_excluded_bounce_total"] == survivor._excluded_bounce_total
