"""Elastic autoscaling (ISSUE 15).

The tentpole control loop end to end: plan() hysteresis/bounds against a
synthetic clock, the spawn circuit breaker provably halting a spawn storm,
grace-window expiry of spawns that never advertise, warm prefix-cache
handoff (batcher-level hot_prefixes -> export -> import round trip, the
worker kv_handoff/kv_import subjects with validation and graceful no-ops on
fake engines), the drained-worker restart suppression satellite, and two
live-broker chaos tests: kill-and-replace under a fake-engine load wave,
and the real-engine acceptance e2e — a killed worker's replacement serves
its first request with persistent-compile-cache hits and a nonzero
prefix-cache hit rate from the donor's warm handoff.
"""

import asyncio
import functools
import json
import time

import jax
import numpy as np
import pytest

from nats_llm_studio_tpu.config import WorkerConfig
from nats_llm_studio_tpu.engine.generator import SamplingParams
from nats_llm_studio_tpu.models.config import ModelConfig
from nats_llm_studio_tpu.models.export import export_params_to_gguf
from nats_llm_studio_tpu.models.llama import init_params
from nats_llm_studio_tpu.obs import compile_cache_counts, install_compile_cache_listener
from nats_llm_studio_tpu.obs.aggregator import Aggregator
from nats_llm_studio_tpu.serve import Autoscaler, Worker
from nats_llm_studio_tpu.serve.batcher import ContinuousBatcher
from nats_llm_studio_tpu.serve.kv_transfer import decode_kv_blob, encode_kv_blob
from nats_llm_studio_tpu.serve.registry import LocalRegistry
from nats_llm_studio_tpu.serve.worker import KV_MODEL_HEADER
from nats_llm_studio_tpu.store.manager import ModelStore
from nats_llm_studio_tpu.transport import EmbeddedBroker, RetryPolicy, connect
from nats_llm_studio_tpu.transport import protocol as p
from nats_llm_studio_tpu.transport.envelope import deadline_header_value

from conftest import async_test
from fakes import FakeRegistry
from test_cluster import ClusterHarness
from test_serve_e2e import byte_level_tokenizer_md

MID = "acme/tiny-autoscale"


def _async_test_long(fn):
    """Like conftest.async_test, with headroom for three real engine loads."""

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        asyncio.run(asyncio.wait_for(fn(*args, **kwargs), timeout=180.0))

    return wrapper


class StubNC:
    """Duck-typed client for pure control-loop tests: records every event
    publish and directed request, answers requests with an ok envelope."""

    def __init__(self):
        self.published: list[tuple[str, dict]] = []
        self.requests: list[tuple[str, dict]] = []

    async def publish(self, subject, payload, headers=None):
        self.published.append((subject, json.loads(payload)))

    async def request(self, subject, payload=b"", timeout=2.0, headers=None,
                      retry=None):
        self.requests.append((subject, json.loads(payload or b"{}")))

        class _Reply:
            payload = b'{"ok":true,"data":{}}'

        return _Reply()

    async def subscribe(self, subject, cb=None, queue=None):
        class _Sub:
            async def unsubscribe(self):
                pass

        return _Sub()


def _adv(wid, depth=0, brownout=0, draining=False):
    return {"worker_id": wid, "queue_depth": depth, "brownout": brownout,
            "draining": draining}


def _seed(a, now, *adverts):
    for d in adverts:
        a._members[d["worker_id"]] = {"mono": now, "advert": d}


def _metric(prom: str, name: str) -> float:
    for line in prom.splitlines():
        if line.startswith(name) and not line.startswith("#"):
            return float(line.rsplit(None, 1)[1])
    raise AssertionError(f"{name} missing from exposition:\n{prom}")


def events(nc: StubNC, action: str) -> list[dict]:
    return [e for _, e in nc.published if e.get("action") == action]


# -- plan(): pure policy against a synthetic clock ----------------------------


def test_plan_scale_up_hysteresis_cooldown_and_max_bound():
    a = Autoscaler(StubNC(), min_workers=1, max_workers=3, up_dwell_s=2.0,
                   down_dwell_s=30.0, cooldown_s=5.0, up_queue_depth=8.0,
                   stale_after_s=1e9, handoff_prefixes=0)
    t = 1000.0
    _seed(a, t, _adv("w-a", depth=9), _adv("w-b", depth=9))
    assert a.plan(t) is None          # pressure noted; the dwell starts
    assert a.plan(t + 1.0) is None    # still dwelling
    # pressure that breaks before the dwell elapses resets the clock
    _seed(a, t, _adv("w-a", depth=2), _adv("w-b", depth=2))
    assert a.plan(t + 1.5) is None
    assert a._pressure_since is None
    _seed(a, t, _adv("w-a", depth=9), _adv("w-b", depth=9))
    assert a.plan(t + 2.0) is None    # dwell restarted here
    d = a.plan(t + 4.0)
    assert d == {"action": "spawn", "reason": "queue_depth avg 9.0",
                 "workers_live": 2}
    # cooldown gates everything, even persisting pressure
    a._cooldown_until = t + 10.0
    assert a.plan(t + 5.0) is None
    # pressed against the ceiling the plan yields to shedding
    a._cooldown_until = -float("inf")
    _seed(a, t, _adv("w-a", 9), _adv("w-b", 9), _adv("w-c", 9))
    assert a.plan(t + 6.0) is None


def test_plan_slo_burn_counts_as_pressure():
    a = Autoscaler(StubNC(), min_workers=1, max_workers=3, up_dwell_s=1.0,
                   cooldown_s=0.0, stale_after_s=1e9, handoff_prefixes=0)
    t = 2000.0
    _seed(a, t, _adv("w-a", depth=0))
    a._last_burn_mono = t             # the aggregator just alerted
    assert a.plan(t) is None
    d = a.plan(t + 1.0)
    assert d is not None and d["action"] == "spawn"
    assert d["reason"] == "slo_burn"


def test_plan_below_min_spawns_immediately_and_counts_pending():
    a = Autoscaler(StubNC(), min_workers=2, max_workers=4, stale_after_s=1e9,
                   handoff_prefixes=0)
    # an empty fleet is replaced NOW — no dwell on a dead worker's absence
    d = a.plan(3000.0)
    assert d == {"action": "spawn", "reason": "below_min", "workers_live": 0}
    # a spawn already in flight counts against the floor (no double-spawn)
    a._pending["w-x"] = {"mono": 3000.0, "proc": None}
    _seed(a, 3000.0, _adv("w-a"))
    assert a.plan(3001.0) is None


def test_plan_scale_down_picks_least_loaded_and_respects_floor():
    a = Autoscaler(StubNC(), min_workers=1, max_workers=4, down_dwell_s=3.0,
                   cooldown_s=0.0, stale_after_s=1e9, handoff_prefixes=0)
    t = 4000.0
    _seed(a, t, _adv("w-a", depth=1), _adv("w-b", depth=0))
    assert a.plan(t) is None          # idle dwell starts
    d = a.plan(t + 3.0)
    assert d == {"action": "drain", "reason": "idle", "victim": "w-b",
                 "workers_live": 2}
    # at the floor nothing drains, however idle
    a2 = Autoscaler(StubNC(), min_workers=1, max_workers=4, down_dwell_s=0.0,
                    stale_after_s=1e9, handoff_prefixes=0)
    _seed(a2, t, _adv("w-only"))
    assert a2.plan(t) is None


# -- tick(): actions, grace expiry, the circuit breaker -----------------------


@async_test
async def test_tick_drain_hands_off_to_best_survivor():
    nc = StubNC()
    drained = []
    a = Autoscaler(nc, min_workers=1, max_workers=4, down_dwell_s=0.0,
                   cooldown_s=0.0, handoff_prefixes=4, stale_after_s=1e9,
                   drain_fn=lambda wid, to: drained.append((wid, to)))
    t = 5000.0
    _seed(a, t, _adv("w-a", depth=1), _adv("w-b", depth=0),
          _adv("w-c", depth=0))
    d = await a.tick(t)
    assert d is not None and d["action"] == "drain" and d["victim"] == "w-b"
    # the victim's hot cache goes to the least-loaded survivor, not nowhere
    assert drained == [("w-b", "w-c")]
    assert a.drains_total == 1
    ev = events(nc, "drain")
    assert len(ev) == 1
    assert ev[0]["kind"] == "autoscale" and ev[0]["handoff_to"] == "w-c"


@async_test
async def test_tick_expires_unadvertised_spawn_and_kills_the_proc():
    class FakeProc:
        killed = False

        def poll(self):
            return None

        def kill(self):
            self.killed = True

    proc = FakeProc()
    a = Autoscaler(StubNC(), min_workers=1, max_workers=4, spawn_grace_s=5.0,
                   cooldown_s=0.0, stale_after_s=1e9, handoff_prefixes=0,
                   spawn_fn=lambda wid: proc)
    t = 6000.0
    d = await a.tick(t)               # below_min: spawn goes pending
    assert d is not None and d["action"] == "spawn"
    assert a.spawns_total == 1 and len(a._pending) == 1
    await a.tick(t + 6.0)             # grace blown: the hung proc dies
    assert proc.killed is True
    assert a.spawn_failures_total == 1
    # below_min re-spawned a fresh pending in the very same tick — the
    # floor is never left unfilled while the breaker is closed
    assert a.spawns_total == 2 and len(a._pending) == 1


@async_test
async def test_first_advert_of_pending_spawn_triggers_warm_handoff():
    nc = StubNC()
    a = Autoscaler(nc, min_workers=2, max_workers=4, cooldown_s=0.0,
                   handoff_prefixes=4, stale_after_s=1e9,
                   spawn_fn=lambda wid: None)
    t = 7000.0
    _seed(a, t, _adv("w-donor", depth=0))
    d = await a.tick(t)               # 1 live < min 2
    assert d is not None and d["reason"] == "below_min"
    wid = next(iter(a._pending))
    a.observe_advert(wid, _adv(wid))
    assert a._pending == {}           # live now; failures streak resets
    assert a._consecutive_failures == 0
    for _ in range(5):                # let the background handoff task land
        await asyncio.sleep(0.01)
    handoffs = [(s, b) for s, b in nc.requests if s.endswith(".kv_handoff")]
    assert handoffs == [
        ("lmstudio.worker.w-donor.kv_handoff", {"to": wid, "limit": 4})
    ]


@async_test
async def test_spawn_circuit_breaker_halts_the_spawn_storm():
    """ISSUE 15 acceptance: consecutive spawn failures open the breaker,
    further wanted spawns are suppressed with ONE reasoned event (no storm,
    no event flood), and spawning resumes after the breaker cooldown."""
    nc = StubNC()
    attempts = []

    def exploding_spawn(wid):
        attempts.append(wid)
        raise RuntimeError("exec format error")

    a = Autoscaler(nc, min_workers=1, max_workers=4, breaker_failures=3,
                   breaker_cooldown_s=100.0, cooldown_s=0.0,
                   stale_after_s=1e9, handoff_prefixes=0,
                   spawn_fn=exploding_spawn)
    t = 8000.0
    for i in range(3):                # empty fleet: below_min every tick
        await a.tick(t + i)
    assert len(attempts) == 3
    assert a.spawn_failures_total == 3
    assert a.breaker_open(t + 3) is True
    prom = a.render_prometheus(now=t + 3)
    assert _metric(prom, "lmstudio_autoscale_spawn_failures_total") == 3
    assert _metric(prom, "lmstudio_autoscale_spawns_total") == 0
    assert _metric(prom, "lmstudio_autoscale_drains_total") == 0
    assert _metric(prom, "lmstudio_autoscale_breaker_open") == 1
    # the storm is halted: seven more pressured ticks attempt nothing
    for i in range(3, 10):
        await a.tick(t + i)
    assert len(attempts) == 3
    await asyncio.sleep(0.02)         # drain the _emit_soon background tasks
    assert len(events(nc, "spawn_failed")) == 3
    suppressed = events(nc, "spawn_suppressed")
    assert len(suppressed) == 1       # announced once, not per tick
    assert suppressed[0]["reason"] == "breaker_open"
    assert suppressed[0]["wanted"] == "below_min"
    # past the cooldown the breaker closes and spawning resumes
    await a.tick(t + 200.0)
    assert len(attempts) == 4
    await a.stop()


# -- warm handoff: batcher-level enumeration + round trip ---------------------


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig.tiny(n_layers=2, max_seq_len=128)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _batcher(params, cfg, **kw):
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("prefix_cache_blocks", 16)
    return ContinuousBatcher(params, cfg, max_slots=4, max_seq_len=64,
                             buckets=[8, 64], paged=True, **kw)


async def _greedy(b, prompt, n=8):
    sp = SamplingParams(temperature=0.0, max_tokens=n)
    return [t async for t in b.submit(list(prompt), sp)]


@async_test
async def test_hot_prefixes_enumerates_mru_first_and_feeds_handoff(model):
    cfg, params = model
    pa = [(i * 7 + 3) % cfg.vocab_size for i in range(16)]  # 2 chunks of 8
    pb = [(i * 5 + 1) % cfg.vocab_size for i in range(16)]
    a, b = _batcher(params, cfg), _batcher(params, cfg)
    try:
        await _greedy(a, pa)
        await _greedy(a, pb)
        hot = a.prefix_cache.hot_prefixes(4)
        assert hot, "a warmed cache enumerated nothing"
        assert hot[0][:16] == pb      # most-recently-used first
        assert any(path[:16] == pa for path in hot)
        assert a.prefix_cache.hot_prefixes(1) == hot[:1]
        assert a.prefix_cache.hot_prefixes(0) == []
        # the enumerated path feeds export directly: the handoff pipeline
        # round-trips into a cold peer...
        export = await asyncio.to_thread(a.export_prefix_blocks, hot[0])
        assert export is not None and export["chunks"]
        imported = await asyncio.to_thread(
            b.import_prefix_blocks, decode_kv_blob(encode_kv_blob(export))
        )
        assert imported["tokens"] == len(export["token_ids"])
        # ...which now admits the hot prompt with a prefix hit
        await _greedy(b, pb)
        assert b.prefix_cache.counters()["hit_tokens"] > 0
    finally:
        a.stop()
        b.stop()


# -- worker subjects on fake engines: validation + graceful no-ops ------------


@async_test
async def test_kv_handoff_and_import_subjects_on_fake_engines():
    async with ClusterHarness(n_workers=2) as h:
        wa, wb = h.workers
        # a handoff between engines with no KV surface is a graceful no-op
        resp, _ = await h.req(f"worker.{wa.worker_id}.kv_handoff",
                              {"to": wb.worker_id})
        assert resp["ok"] is True
        assert resp["data"] == {"to": wb.worker_id, "sent": 0, "failed": 0,
                                "tokens": 0}
        # validation
        resp, _ = await h.req(f"worker.{wa.worker_id}.kv_handoff", {})
        assert resp["ok"] is False and "'to' is required" in resp["error"]
        resp, _ = await h.req(f"worker.{wa.worker_id}.kv_handoff",
                              {"to": wa.worker_id})
        assert resp["ok"] is False and "self" in resp["error"]
        resp, _ = await h.req(f"worker.{wa.worker_id}.kv_handoff",
                              {"to": wb.worker_id, "limit": "lots"})
        assert resp["ok"] is False and "integer" in resp["error"]
        # kv_import: a raw blob must name its model in the header
        resp, _ = await h.req(f"worker.{wa.worker_id}.kv_import", b"KVX1junk")
        assert resp["ok"] is False and KV_MODEL_HEADER in resp["error"]
        # a corrupt blob is a counted transfer failure, not a crash
        resp, _ = await h.req(f"worker.{wa.worker_id}.kv_import", b"KVX1junk",
                              headers={KV_MODEL_HEADER: "fake-echo-1"})
        assert resp["ok"] is False and "error in kv import" in resp["error"]
        assert wa._kv_transfer_failures == 1
        # an object-store ref missing its fields is rejected up front
        resp, _ = await h.req(f"worker.{wa.worker_id}.kv_import",
                              {"model": "fake-echo-1"})
        assert resp["ok"] is False and "'model' and 'object'" in resp["error"]
        # a well-formed blob into an engine with no import hook: graceful
        export = {"token_ids": list(range(8)), "chunk_tokens": 8,
                  "chunks": [{"k": np.zeros((1, 2, 8, 2, 4), np.float32),
                              "v": np.zeros((1, 2, 8, 2, 4), np.float32)}]}
        resp, _ = await h.req(f"worker.{wa.worker_id}.kv_import",
                              encode_kv_blob(export),
                              headers={KV_MODEL_HEADER: "fake-echo-1"})
        assert resp["ok"] is True
        assert resp["data"] == {"imported": False, "reason": "no_import"}
        # the families exist even at zero, so dashboards can assert on them
        prom = (await h.nc.request(
            f"lmstudio.worker.{wb.worker_id}.metrics.prom", b"", timeout=10
        )).payload.decode()
        assert "lmstudio_warm_handoff_sent_total" in prom
        assert "lmstudio_warm_handoff_received_total" in prom


@async_test
async def test_admin_drain_carries_handoff_to():
    async with ClusterHarness(n_workers=2) as h:
        wa, wb = h.workers
        resp, _ = await h.req("admin.drain", {"worker_id": wa.worker_id,
                                              "handoff_to": wb.worker_id})
        assert resp["ok"] is True
        assert resp["data"]["draining"] is True
        # fake engines hand nothing over, but the handoff rode the drain
        assert resp["data"]["handoff"] == {"to": wb.worker_id, "sent": 0,
                                           "failed": 0, "tokens": 0}


# -- the drained-worker restart suppression satellite -------------------------


class _StubEngine:
    batcher = None

    async def unload(self):
        pass


@async_test
async def test_restart_engine_suppressed_while_draining(tmp_path):
    reg = LocalRegistry(ModelStore(tmp_path / "models"), restart_backoff_s=0.2)
    reg._engines["m"] = _StubEngine()
    # entry guard: a draining registry refuses before any teardown
    reg.set_draining(True)
    assert await reg.restart_engine("m") == "draining"
    assert "m" in reg._engines
    # post-backoff guard: the drain lands while the restart sleeps out its
    # backoff — the engine is torn down but never resurrected
    reg.set_draining(False)
    task = asyncio.ensure_future(reg.restart_engine("m", reason="hung"))
    await asyncio.sleep(0.05)
    reg.set_draining(True)
    assert await task == "draining"
    assert "m" not in reg._engines
    assert reg.engine_restarts_total == 0


# -- the autoscaler's exposition rides the cluster scrape ---------------------


def test_aggregator_merges_autoscaler_exposition():
    a = Autoscaler(StubNC(), handoff_prefixes=0)
    agg = Aggregator(None, extra_expositions=[a.render_prometheus])
    text = agg.render_cluster()
    assert "lmstudio_autoscale_spawns_total" in text
    assert "lmstudio_autoscale_breaker_open" in text
    # a broken extra source must not break the scrape
    agg2 = Aggregator(
        None, extra_expositions=[lambda: 1 / 0, a.render_prometheus]
    )
    assert "lmstudio_autoscale_spawns_total" in agg2.render_cluster()


# -- kill-and-replace under load (fake engines, real broker) ------------------


@async_test
async def test_kill_and_replace_under_load():
    """Sever a worker mid-wave: every request is served (retries absorb the
    kill — zero timeout expiries), the autoscaler detects the dead member
    via advert staleness and spawns a replacement below the floor."""
    async with ClusterHarness(n_workers=2, advert_interval_s=0.05) as h:
        spawned = []

        async def spawn_fn(wid):
            w = Worker(
                WorkerConfig(nats_url=h.broker.url, worker_id=wid,
                             cluster_advert_interval_s=0.05),
                FakeRegistry(),
            )
            await w.start()
            spawned.append(w)

        a = Autoscaler(h.nc, min_workers=2, max_workers=3, interval_s=0.05,
                       stale_after_s=0.4, spawn_grace_s=10.0, cooldown_s=0.3,
                       handoff_prefixes=0, spawn_fn=spawn_fn)
        # steady-state start: subscribe first, let both members advertise,
        # THEN run the loop — under a loaded CPU the loop's settle window
        # alone may not outlast the first adverts
        await a.start(control_loop=False)
        try:
            deadline = time.monotonic() + 5.0
            while len(a.live_workers()) < 2 and time.monotonic() < deadline:
                await asyncio.sleep(0.02)
            assert len(a.live_workers()) == 2
            a._task = asyncio.ensure_future(a._loop())

            async def one(i):
                body = json.dumps(h.chat(f"r{i}")).encode()
                msg = await h.nc.request(
                    "lmstudio.chat_model", body, timeout=1.0,
                    headers={p.DEADLINE_HEADER: deadline_header_value(20.0)},
                    retry=RetryPolicy(max_attempts=40, backoff_s=0.05,
                                      jitter=0.0, retry_on_timeout=True),
                )
                return json.loads(msg.payload)

            wave = [asyncio.ensure_future(one(i)) for i in range(12)]
            await asyncio.sleep(0.1)
            await h.workers[0].nc.close()   # kill: no drain, no goodbye
            results = await asyncio.gather(*wave)
            assert all(r["ok"] for r in results), results

            deadline = time.monotonic() + 10.0
            while ((a.spawns_total < 1 or len(a.live_workers()) < 2)
                   and time.monotonic() < deadline):
                await asyncio.sleep(0.05)
            assert a.spawns_total >= 1
            assert a.spawn_failures_total == 0
            assert len(a.live_workers()) >= 2
            assert any(w.worker_id.startswith("w-as") for w in spawned)
            prom = a.render_prometheus()
            assert _metric(prom, "lmstudio_autoscale_spawns_total") >= 1
        finally:
            await a.stop()
            for w in spawned:
                await w.drain()


@async_test
async def test_pull_precompile_transcript_stable_and_unloads(tmp_path):
    """Pull-time precompile is invisible on the wire: the pull reply stays
    exactly the store transcript ("pulled"), and an engine loaded only for
    the compile is unloaded on the way out — pull leaves the model
    cached-not-loaded while the compiled programs persist on disk. A model
    that was already resident stays resident."""
    from nats_llm_studio_tpu.serve import registry as registry_mod

    store = ModelStore(tmp_path / "models")
    reg = LocalRegistry(store, dtype="float32", pull_precompile=True)
    calls = {"warm": 0, "unload": 0}

    class _Batcher:
        def warm_chunk_programs(self):
            calls["warm"] += 1
            return 3

    class _Engine:
        batcher = _Batcher()

        async def unload(self):
            calls["unload"] += 1

    eng = _Engine()

    async def fake_pull(identifier):
        return tmp_path / "models" / identifier, "pulled"

    async def fake_get_engine(model_id):
        reg._engines[model_id] = eng
        return eng

    store.pull = fake_pull
    reg.get_engine = fake_get_engine
    reg._mesh_unservable = lambda path: None
    real_gate = registry_mod._compile_cache_dir_configured
    registry_mod._compile_cache_dir_configured = lambda: True
    try:
        out = await reg.pull("acme/tiny")
        assert out == "pulled"                 # wire transcript untouched
        assert calls["warm"] == 1              # the grid WAS compiled
        assert calls["unload"] == 1            # load served only the compile
        assert "acme/tiny" not in reg.loaded_engines()

        # already resident: the re-pull re-warms but must not unload
        reg._engines["acme/tiny"] = eng
        out = await reg.pull("acme/tiny")
        assert out == "pulled"
        assert calls["warm"] == 2
        assert calls["unload"] == 1
        assert "acme/tiny" in reg.loaded_engines()
    finally:
        registry_mod._compile_cache_dir_configured = real_gate


# -- the acceptance e2e: real engines, kill, precompiled + warm replacement ---


def _publish_tiny(models_dir, model_id=MID, seed=11):
    cfg = ModelConfig.tiny(n_layers=2, max_seq_len=64)
    params = init_params(cfg, jax.random.PRNGKey(seed))
    d = models_dir / model_id
    d.mkdir(parents=True, exist_ok=True)
    export_params_to_gguf(
        d / "m.gguf", params, cfg, name=model_id,
        tokenizer_md=byte_level_tokenizer_md(cfg.vocab_size),
    )


def _registry(models):
    return LocalRegistry(
        ModelStore(models), dtype="float32", max_batch_slots=2,
        max_seq_len=64, prefill_chunk=8, prefix_cache_blocks=16,
    )


def _chat_body(text, max_tokens=8):
    return json.dumps({
        "model": MID,
        "messages": [{"role": "user", "content": text}],
        "max_tokens": max_tokens,
        "temperature": 0.0,
    }).encode()


@_async_test_long
async def test_autoscaler_replaces_killed_worker_with_warm_replacement(tmp_path):
    """ISSUE 15 acceptance: under a request wave, killing a worker triggers
    an autoscaler spawn; the replacement's first serve hits the persistent
    XLA compile cache AND the prefix cache warmed by the donor's kv_handoff
    push, and every wave request is served or cleanly retryable."""
    install_compile_cache_listener()
    # persist every program, as ``serve`` does (config.configure_jax; these
    # in-process workers skip it): the tiny model's sub-second compiles would
    # otherwise never reach the cache, and the hits asserted below would
    # depend on what earlier runs happened to leave in it
    min_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    models = tmp_path / "models"
    _publish_tiny(models)
    broker = await EmbeddedBroker().start()
    try:
        # donor and victim share one registry: one engine load covers both
        shared = _registry(models)
        donor = Worker(
            WorkerConfig(nats_url=broker.url, worker_id="w-donor",
                         cluster_advert_interval_s=0.1,
                         kv_transfer_timeout_s=120.0),
            shared,
        )
        victim = Worker(
            WorkerConfig(nats_url=broker.url, worker_id="w-victim",
                         cluster_advert_interval_s=0.1,
                         kv_transfer_timeout_s=120.0),
            shared,
        )
        await donor.start()
        await victim.start()
        nc = await connect(broker.url)

        # warm the donor: load the engine, seed its radix cache
        warm_body = _chat_body("warm the handoff path")
        env = json.loads((await nc.request(
            "lmstudio.worker.w-donor.chat_model", warm_body, timeout=120
        )).payload)
        assert env["ok"] is True, env
        assert shared.loaded_engines()[MID].batcher.prefix_cache.blocks > 0

        spawned = []

        async def spawn_fn(wid):
            w = Worker(
                WorkerConfig(nats_url=broker.url, worker_id=wid,
                             cluster_advert_interval_s=0.1,
                             kv_transfer_timeout_s=120.0),
                _registry(models),
            )
            await w.start()
            spawned.append(w)

        scaler = Autoscaler(nc, min_workers=2, max_workers=3, interval_s=0.1,
                            stale_after_s=0.6, spawn_grace_s=60.0,
                            cooldown_s=1.0, handoff_prefixes=4,
                            spawn_fn=spawn_fn)
        await scaler.start()
        try:
            deadline = time.monotonic() + 10.0
            while (len(scaler.live_workers()) < 2
                   and time.monotonic() < deadline):
                await asyncio.sleep(0.05)
            assert sorted(scaler.live_workers()) == ["w-donor", "w-victim"]
            cc_before = compile_cache_counts()

            async def one(i):
                body = _chat_body(f"wave request number {i:02d}")
                msg = await nc.request(
                    "lmstudio.chat_model", body, timeout=5.0,
                    headers={p.DEADLINE_HEADER: deadline_header_value(90.0)},
                    retry=RetryPolicy(max_attempts=10, backoff_s=0.1,
                                      jitter=0.0, retry_on_timeout=True),
                )
                return json.loads(msg.payload)

            wave = [asyncio.ensure_future(one(i)) for i in range(6)]
            await asyncio.sleep(0.2)
            await victim.nc.close()     # the kill
            results = await asyncio.gather(*wave)
            # served or cleanly retryable — never a timeout expiry (gather
            # would have raised) or a non-retryable error
            assert all(r["ok"] or r.get("retryable") for r in results), results
            assert any(r["ok"] for r in results)

            # the autoscaler notices the stale member, spawns a replacement,
            # and fires the donor's warm handoff at its first advert
            deadline = time.monotonic() + 90.0
            while time.monotonic() < deadline:
                if spawned and spawned[0]._warm_handoff_received >= 1:
                    break
                await asyncio.sleep(0.1)
            assert scaler.spawns_total >= 1
            assert spawned, "the autoscaler never spawned a replacement"
            repl = spawned[0]
            assert repl._warm_handoff_received >= 1
            assert donor._warm_handoff_sent >= 1

            # first serve on the replacement: prefix hits from the handoff,
            # jit programs from the persistent compile cache
            env = json.loads((await nc.request(
                f"lmstudio.worker.{repl.worker_id}.chat_model", warm_body,
                timeout=120,
            )).payload)
            assert env["ok"] is True, env
            ctr = repl.registry.loaded_engines()[MID].batcher \
                .prefix_cache.counters()
            assert ctr["hits"] >= 1 and ctr["hit_tokens"] > 0
            cc_after = compile_cache_counts()
            assert cc_after["hits"] > cc_before["hits"]
            prom = scaler.render_prometheus()
            assert _metric(prom, "lmstudio_autoscale_spawns_total") >= 1
        finally:
            await scaler.stop()
            for w in spawned:
                await w.drain()
        await nc.close()
        await donor.drain()
        await victim.drain()
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", min_secs)
        await broker.stop()
