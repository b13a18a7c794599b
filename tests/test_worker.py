"""Worker handler-layer tests over real (embedded) NATS with a fake engine —
the integration tier SURVEY.md §4.2 specifies. Exercises every validation
branch of the reference handlers (nats_llm_studio.go:254-262, :293-300,
:331-345), the envelope contract, queue-group scale-out with two workers, and
token streaming."""

import asyncio
import collections
import json

from nats_llm_studio_tpu.config import WorkerConfig
from nats_llm_studio_tpu.serve import Worker
from nats_llm_studio_tpu.transport import EmbeddedBroker, connect

from conftest import async_test
from fakes import FakeRegistry


class Harness:
    def __init__(self, n_workers=1, models=None, delay_s=0.0):
        self.n_workers = n_workers
        self.models = models
        self.delay_s = delay_s

    async def __aenter__(self):
        self.broker = await EmbeddedBroker().start()
        self.registries = []
        self.workers = []
        for _ in range(self.n_workers):
            reg = FakeRegistry(models=self.models, delay_s=self.delay_s)
            w = Worker(WorkerConfig(nats_url=self.broker.url), reg)
            await w.start()
            self.registries.append(reg)
            self.workers.append(w)
        self.nc = await connect(self.broker.url)
        return self

    async def __aexit__(self, *exc):
        await self.nc.close()
        for w in self.workers:
            await w.drain()
        await self.broker.stop()

    async def req(self, op: str, payload, timeout=5.0):
        body = payload if isinstance(payload, bytes) else json.dumps(payload).encode()
        msg = await self.nc.request(f"lmstudio.{op}", body, timeout=timeout)
        return json.loads(msg.payload)


@async_test
async def test_chat_loads_under_the_pull_deadline_then_chats_under_its_own():
    """The deadline ladder of chat_model: getting the model ready is a
    pull-class step (an 8B int8 load is minutes) and runs under
    pull_timeout_s; chat_timeout_s starts once the engine exists."""
    broker = await EmbeddedBroker().start()
    reg = FakeRegistry(models=["m1"])
    real_get = reg.get_engine

    async def slow_get(model_id):
        await asyncio.sleep(0.6)  # longer than the chat deadline below
        return await real_get(model_id)

    reg.get_engine = slow_get
    cfg = WorkerConfig(nats_url=broker.url)
    cfg.chat_timeout_s, cfg.pull_timeout_s = 0.3, 5.0
    w = Worker(cfg, reg)
    await w.start()
    nc = await connect(broker.url)
    try:
        body = json.dumps({"model": "m1", "messages": [
            {"role": "user", "content": "hi"}]}).encode()
        resp = json.loads((await nc.request("lmstudio.chat_model", body, timeout=10)).payload)
        assert resp["ok"] is True, resp
        # a load that outlasts the pull deadline is still a deadline error
        cfg.pull_timeout_s = 0.2
        resp = json.loads((await nc.request("lmstudio.chat_model", body, timeout=10)).payload)
        assert resp["ok"] is False
        assert resp["error"] == "error in chat: deadline exceeded"
    finally:
        await nc.close()
        await w.drain()
        await broker.stop()


@async_test
async def test_list_models_envelope():
    async with Harness(models=["m1", "m2"]) as h:
        resp = await h.req("list_models", {})
        assert resp["ok"] is True
        assert "error" not in resp
        assert resp["data"]["http_status"] == 200
        ids = [m["id"] for m in resp["data"]["models"]["data"]]
        assert sorted(ids) == ["m1", "m2"]
        assert resp["data"]["models"]["object"] == "list"


@async_test
async def test_pull_model_validation_and_success():
    async with Harness() as h:
        resp = await h.req("pull_model", {})
        assert resp["ok"] is False and resp["error"] == "'identifier' is required"

        resp = await h.req("pull_model", b"{not json")
        assert resp["ok"] is False and resp["error"].startswith("invalid JSON in PullModel")

        resp = await h.req("pull_model", {"identifier": "pub/new-model"})
        assert resp["ok"] is True
        assert resp["data"]["model"] == "pub/new-model"
        assert "output" in resp["data"]
        assert h.registries[0].pulled == ["pub/new-model"]


@async_test
async def test_delete_model_validation_success_and_missing_dir():
    async with Harness(models=["m1"]) as h:
        resp = await h.req("delete_model", {})
        assert resp["ok"] is False and resp["error"] == "'model_id' is required"

        resp = await h.req("delete_model", {"model_id": "m1"})
        assert resp["ok"] is True
        assert resp["data"]["model"] == "m1"
        assert resp["data"]["deleted_dir"].endswith("m1")

        # missing model: error carries the attempted dir (go :304-313)
        resp = await h.req("delete_model", {"model_id": "ghost"})
        assert resp["ok"] is False
        assert "model directory not found" in resp["error"]
        assert resp["data"]["dir"].endswith("ghost")


@async_test
async def test_chat_model_validation_branches():
    async with Harness() as h:
        resp = await h.req("chat_model", b"")
        assert resp["ok"] is False and "empty payload" in resp["error"]

        resp = await h.req("chat_model", b"not json at all")
        assert resp["ok"] is False and resp["error"].startswith("invalid JSON in ChatModel")

        resp = await h.req("chat_model", {"messages": []})
        assert resp["ok"] is False and resp["error"] == "'model' is required in ChatModel"

        resp = await h.req("chat_model", {"model": "nope", "messages": []})
        assert resp["ok"] is False and "model not found" in resp["error"]


@async_test
async def test_chat_model_success_shape():
    async with Harness() as h:
        payload = {
            "model": "fake-echo-1",
            "messages": [
                {"role": "system", "content": "Always answer in rhymes."},
                {"role": "user", "content": "hello tpu"},
            ],
        }
        resp = await h.req("chat_model", payload)
        assert resp["ok"] is True
        data = resp["data"]
        assert data["http_status"] == 200
        response = data["response"]
        assert response["object"] == "chat.completion"
        assert response["choices"][0]["message"]["content"] == "echo: hello tpu"
        assert response["usage"]["completion_tokens"] == 3
        assert response["usage"]["total_tokens"] > 3


@async_test
async def test_chat_model_streaming():
    async with Harness() as h:
        payload = {
            "model": "fake-echo-1",
            "stream": True,
            "messages": [{"role": "user", "content": "a b c"}],
        }
        chunks, final = [], None
        async for m in h.nc.request_stream("lmstudio.chat_model", json.dumps(payload).encode(), timeout=10):
            body = json.loads(m.payload)
            if m.headers and "Nats-Stream-Done" in m.headers:
                final = body
            else:
                chunks.append(body["data"]["chunk"])
        assert final is not None and final["ok"] is True
        text = "".join(c["choices"][0]["delta"]["content"] for c in chunks)
        assert text.strip() == "echo: a b c"
        assert final["data"]["response"]["choices"][0]["message"]["content"] == "echo: a b c"


@async_test
async def test_health_subject():
    async with Harness(models=["m1"]) as h:
        resp = await h.req("health", {})
        assert resp["ok"] is True
        assert resp["data"]["status"] == "ok"
        assert resp["data"]["models_loaded"] == ["m1"]
        assert resp["data"]["queue_group"] == "lmstudio-workers"


@async_test
async def test_sync_model_from_bucket_subject():
    async with Harness() as h:
        resp = await h.req("sync_model_from_bucket", {})
        assert resp["ok"] is False and resp["error"] == "'object_name' is required"

        resp = await h.req("sync_model_from_bucket", {"object_name": "pub/model/file.gguf"})
        assert resp["ok"] is True
        assert resp["data"]["local_path"].endswith("pub/model/file.gguf")


@async_test
async def test_two_workers_queue_group_scale_out():
    """README.md:478-484: multiple workers under one queue group split load;
    each request is answered exactly once."""
    async with Harness(n_workers=2) as h:
        N = 40
        results = await asyncio.gather(
            *[
                h.req("chat_model", {"model": "fake-echo-1", "messages": [{"role": "user", "content": f"r{i}"}]})
                for i in range(N)
            ]
        )
        assert all(r["ok"] for r in results)
        served = collections.Counter()
        for i, w in enumerate(h.workers):
            served[i] = w._requests_total
        assert sum(served.values()) == N
        assert all(v > 0 for v in served.values()), f"load not balanced: {served}"


@async_test
async def test_unexpected_exception_still_replies_error_envelope():
    """An exception escaping a handler (not EngineError) must produce an
    error envelope, not leave the requester to time out — the reference
    replies on every failure path (nats_llm_studio.go:207-226)."""

    class ExplodingRegistry(FakeRegistry):
        async def list_models(self):
            raise RuntimeError("boom")

    broker = await EmbeddedBroker().start()
    try:
        w = Worker(WorkerConfig(nats_url=broker.url), ExplodingRegistry())
        await w.start()
        nc = await connect(broker.url)
        msg = await nc.request("lmstudio.list_models", b"{}", timeout=5.0)
        resp = json.loads(msg.payload)
        assert resp["ok"] is False
        assert "internal error" in resp["error"] and "boom" in resp["error"]
        await nc.close()
        await w.drain()
    finally:
        await broker.stop()


@async_test
async def test_metrics_subject():
    """metrics — full observability snapshot: worker totals, registry stats,
    per-engine batcher counters, device list (SURVEY.md §5)."""
    async with Harness() as h:
        resp = await h.req("metrics", {})
        assert resp["ok"] is True
        d = resp["data"]
        assert d["requests_total"] >= 0
        assert "registry" in d and "engines" in d
        assert isinstance(d["devices"], list) and d["devices"]
        assert {"id", "platform", "kind"} <= set(d["devices"][0])


@async_test
async def test_profile_subject(tmp_path):
    """profile — captures a jax.profiler trace and replies with its path.
    A client-supplied 'dir' must be IGNORED (round-2 advisor, medium: bus
    clients are untrusted; an honored path would be an arbitrary-directory
    write primitive on the worker host)."""
    import os

    async with Harness() as h:
        client_dir = tmp_path / "client-chosen"
        resp = await h.req(
            "profile", {"seconds": 0.2, "dir": str(client_dir)}, timeout=30.0
        )
        assert resp["ok"] is True
        trace_dir = resp["data"]["trace_dir"]
        assert os.path.isdir(trace_dir)
        assert not client_dir.exists()  # the client's path was not honored
        assert not str(trace_dir).startswith(str(tmp_path))
        found = []
        for root, _, files in os.walk(trace_dir):
            found += files
        assert found  # a trace artifact was written
        bad = await h.req("profile", {"seconds": "xx"})
        assert bad["ok"] is False
        nan = await h.req("profile", b'{"seconds": NaN}')
        assert nan["ok"] is False and "finite" in nan["error"]
