"""Efficiency plane (ISSUE 16): program classes, the device-time ledger's
outcome attribution through real shed/cancel/spec paths, HBM drift gating,
and the merged cluster exposition carrying the fleet ledger families.

The batcher tests drive real served / cancelled / deadline-aborted /
speculative requests and assert the ledger's per-category device-ms reconcile
with the measured dispatch time within 10%; nothing else holds that
invariant.
"""

import asyncio
import time

import jax
import jax.numpy as jnp
import pytest

from nats_llm_studio_tpu.engine.generator import SamplingParams
from nats_llm_studio_tpu.models.config import ModelConfig
from nats_llm_studio_tpu.models.llama import init_params
from nats_llm_studio_tpu.obs.aggregator import merge_expositions
from nats_llm_studio_tpu.obs.roofline import (
    WASTE_CATEGORIES,
    HbmLedger,
    classify_program,
    dispatch_shape_key,
    efficiency_enabled,
)
from nats_llm_studio_tpu.serve.batcher import BatcherOverloaded, ContinuousBatcher, _Request

from conftest import async_test


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig.tiny(n_layers=2, max_seq_len=64)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


async def _wait_for(pred, timeout=10.0, what=""):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        await asyncio.sleep(0.01)
    raise AssertionError(f"timed out waiting for {what}")


# -- switch, program classes, shape keys --------------------------------------


def test_efficiency_kill_switch(monkeypatch):
    monkeypatch.delenv("EFFICIENCY", raising=False)
    assert efficiency_enabled()
    for off in ("0", "false", "OFF", " no "):
        monkeypatch.setenv("EFFICIENCY", off)
        assert not efficiency_enabled()
    monkeypatch.setenv("EFFICIENCY", "1")
    assert efficiency_enabled()


def test_classify_program():
    assert classify_program("prefill_full") == "prefill"
    assert classify_program("admit_fused_paged") == "prefill"
    assert classify_program("decode_pos") == "decode"
    assert classify_program("spec_verify") == "decode"
    assert classify_program("ring_compact") == "other"
    assert set(WASTE_CATEGORIES) >= {"served", "spec_rejected", "other"}


def test_dispatch_shape_key_buckets():
    a = jnp.ones((8, 4), jnp.float32)
    b = jnp.ones((8, 4), jnp.float32)
    c = jnp.ones((16, 4), jnp.float32)
    assert dispatch_shape_key((a, 3), {}) == dispatch_shape_key((b, 3), {})
    assert dispatch_shape_key((a,), {}) != dispatch_shape_key((c,), {})
    assert dispatch_shape_key((a,), {"k": 1}) != dispatch_shape_key((a,), {"k": 2})


# -- HBM ledger ---------------------------------------------------------------


def _ledger(samples, **kw):
    """HbmLedger over a scripted bytes_in_use sequence; events recorded."""
    it = iter(samples)
    events = []
    led = HbmLedger(
        {"weights": lambda: 1000},
        bytes_in_use_fn=lambda: next(it),
        drift_threshold_bytes=kw.pop("threshold", 100),
        sustain_ticks=kw.pop("sustain", 3),
        emit_fn=lambda kind, **f: events.append((kind, f)),
    )
    return led, events


def test_hbm_ledger_fires_once_then_rebaselines():
    # unexplained = in_use - 1000; baseline anchors at the first tick (=0)
    grow = [1000, 1200, 1300, 1400, 1400, 1400, 1400]
    led, events = _ledger(grow)
    for _ in grow:
        led.tick()
    assert led.drift_events == 1
    assert [k for k, _ in events] == ["hbm_drift"]
    assert events[0][1]["unexplained_bytes"] == 400
    # re-baselined at 400: the stable-but-larger footprint never re-fires
    s = led.last_sample()
    assert s["bytes_in_use"] == 1400 and s["priced_bytes"] == 1000
    assert s["drift_bytes"] == 0


def test_hbm_ledger_no_fire_below_threshold_or_nonmonotone():
    # oscillates: each dip resets the sustain counter
    led, events = _ledger([1000, 1250, 1100, 1250, 1100, 1250, 1100, 1250])
    for _ in range(8):
        led.tick()
    assert led.drift_events == 0 and not events
    # steady growth but under the threshold
    led2, events2 = _ledger([1000, 1030, 1060, 1090, 1099, 1099])
    for _ in range(6):
        led2.tick()
    assert led2.drift_events == 0 and not events2


def test_hbm_ledger_cpu_backend_is_inert():
    led = HbmLedger(
        {"weights": lambda: 1 << 30},
        bytes_in_use_fn=lambda: None,
        drift_threshold_bytes=1,
        sustain_ticks=1,
    )
    for _ in range(5):
        assert led.tick() == 0
    assert led.drift_events == 0
    s = led.last_sample()
    assert s["bytes_in_use"] == 0 and s["unexplained_bytes"] == 0
    assert s["priced_bytes"] == 1 << 30  # components still priced/reported


def test_hbm_ledger_broken_component_prices_zero():
    def boom():
        raise RuntimeError("pool gone")

    led = HbmLedger({"pool": boom}, bytes_in_use_fn=lambda: 500,
                    drift_threshold_bytes=10**9)
    led.tick()
    assert led.last_sample()["components"] == {"pool": 0}


# -- device-time ledger through real batcher paths ----------------------------


def _reconcile(stats):
    """Assert the ledger's attributed ms sum to the measured dispatch time
    within 10%, and return the
    per-category snapshot."""
    dt = stats.device_time_snapshot()
    ledger_ms = sum(dt["ms"].values())
    busy_ms = stats.dispatch_ms_total
    assert busy_ms > 0.0
    assert abs(ledger_ms - busy_ms) <= 0.10 * busy_ms, (dt["ms"], busy_ms)
    return dt


@async_test
async def test_ledger_attributes_served_and_cancelled(model):
    cfg, params = model
    b = ContinuousBatcher(params, cfg, max_slots=2, max_seq_len=64, buckets=[8, 64])
    try:
        out = [t async for t in b.submit([1, 2, 3], SamplingParams(
            temperature=0.0, max_tokens=8))]
        assert len(out) == 8

        agen = b.submit_batched([4, 5, 6], SamplingParams(
            temperature=0.0, max_tokens=60))
        got = 0
        async for batch in agen:
            got += len(batch)
            if got >= 2:
                break
        await agen.aclose()
        await _wait_for(
            lambda: all(s is None for s in b._slots) and b.stats.cancelled == 1,
            what="slot freed after close",
        )
        dt = _reconcile(b.stats)
        assert dt["ms"]["served"] > 0.0
        assert dt["ms"]["cancelled"] > 0.0, dt["ms"]
        # tokens count toward goodput only for the served outcome
        assert dt["tokens"]["served"] >= 8
        assert b.stats.goodput_tokens_per_device_s() > 0.0
    finally:
        b.stop()


@async_test
async def test_ledger_attributes_mid_decode_deadline_abort(model):
    cfg, params = model
    b = ContinuousBatcher(params, cfg, max_slots=2, max_seq_len=64, buckets=[8, 64])
    try:
        agen = b.submit_batched([1, 2, 3], SamplingParams(
            temperature=0.0, max_tokens=60), deadline=time.monotonic() + 300.0)
        poked = False
        with pytest.raises(BatcherOverloaded):
            async for _batch in agen:
                if poked:
                    continue
                req = next((s for s in b._slots if isinstance(s, _Request)), None)
                if req is not None:
                    req.deadline = time.monotonic() - 0.001
                    poked = True
        await _wait_for(
            lambda: all(s is None for s in b._slots),
            what="slot freed after deadline abort",
        )
        dt = _reconcile(b.stats)
        assert dt["ms"]["deadline_abort"] > 0.0, dt["ms"]
        assert dt["ms"]["served"] == 0.0  # nothing completed: all waste
        assert b.stats.goodput_tokens_per_device_s() == 0.0
    finally:
        b.stop()


@async_test
async def test_ledger_attributes_spec_rejected(model):
    """Speculative decoding on a repetition-heavy prompt: verify dispatches
    run, and any drafted-but-rejected fraction of their device time lands in
    'spec_rejected' while the ledger still reconciles."""
    cfg, params = model
    REP = [5, 6, 7, 5, 6, 7, 5, 6, 7, 5, 6]
    b = ContinuousBatcher(
        params, cfg, max_slots=2, max_seq_len=64, buckets=[8, 64],
        spec_decode_k=4, decode_burst=1,
    )
    try:
        out = [t async for t in b.submit(REP, SamplingParams(
            temperature=0.0, max_tokens=24))]
        assert len(out) == 24
        snap = b.stats.snapshot()
        assert snap["spec_verifies"] > 0
        dt = _reconcile(b.stats)
        assert dt["ms"]["served"] > 0.0
        if snap["spec_drafted"] > snap["spec_accepted"]:
            assert dt["ms"]["spec_rejected"] > 0.0, (snap, dt["ms"])
    finally:
        b.stop()


@async_test
async def test_ledger_waste_tag_reclassifies_prefill(model):
    """A request submitted with waste_tag='disagg_fallback_reprefill' (the
    worker's failed-KV-prefetch marker) charges its prefill device-ms to that
    category instead of 'served' — decode ms still counts as served."""
    cfg, params = model
    b = ContinuousBatcher(params, cfg, max_slots=2, max_seq_len=64, buckets=[8, 64])
    try:
        out = [t async for t in b.submit([9, 8, 7, 6], SamplingParams(
            temperature=0.0, max_tokens=6), waste_tag="disagg_fallback_reprefill")]
        assert len(out) == 6
        dt = _reconcile(b.stats)
        assert dt["ms"]["disagg_fallback_reprefill"] > 0.0, dt["ms"]
        assert dt["ms"]["served"] > 0.0  # the decode half is real goodput
        assert dt["tokens"]["served"] == 6
    finally:
        b.stop()


# -- cluster rollup -----------------------------------------------------------


def test_merge_expositions_averages_ratio_gauges():
    """Two workers at 400 and 200 tokens per device-second merge to 300, not
    600 — while totals (counters) still sum."""
    w1 = (
        "# TYPE lmstudio_goodput_tokens_per_device_s gauge\n"
        'lmstudio_goodput_tokens_per_device_s{model="m",worker_id="w1"} 400\n'
        "# TYPE lmstudio_device_ms_total counter\n"
        'lmstudio_device_ms_total{category="served",worker_id="w1"} 100\n'
    )
    w2 = (
        "# TYPE lmstudio_goodput_tokens_per_device_s gauge\n"
        'lmstudio_goodput_tokens_per_device_s{model="m",worker_id="w2"} 200\n'
        "# TYPE lmstudio_device_ms_total counter\n"
        'lmstudio_device_ms_total{category="served",worker_id="w2"} 50\n'
    )
    merged = merge_expositions([w1, w2])
    assert 'lmstudio_goodput_tokens_per_device_s{model="m"} 300' in merged
    assert 'lmstudio_device_ms_total{category="served"} 150' in merged


@async_test
async def test_cluster_exposition_carries_efficiency_families(tmp_path, monkeypatch):
    """Acceptance e2e: after one real chat, the aggregator's merged cluster
    exposition carries fleet lmstudio_device_ms_total{category} /
    lmstudio_goodput_tokens_per_device_s families plus the gateway's lmstudio_gateway_* (folded in via the
    gateway's advert + directed metrics.prom subject), and the whole text
    passes the strict Prometheus checker. Gateway adverts must NOT count as
    workers in the router or the cluster gauge."""
    from nats_llm_studio_tpu.config import WorkerConfig
    from nats_llm_studio_tpu.gateway import Gateway
    from nats_llm_studio_tpu.obs.aggregator import Aggregator
    from nats_llm_studio_tpu.serve import Worker
    from nats_llm_studio_tpu.transport import EmbeddedBroker, connect

    from test_disagg import MID, _publish_tiny, _registry
    from test_gateway import _read_response, _send
    from test_obs import check_prom_exposition

    monkeypatch.setenv("GATEWAY_ADVERT_INTERVAL_S", "0.05")
    models = tmp_path / "models"
    _publish_tiny(models)
    broker = await EmbeddedBroker().start()
    w = gw = agg = nc = None
    try:
        w = Worker(
            WorkerConfig(nats_url=broker.url, worker_id="w-eff",
                         cluster_advert_interval_s=0.05),
            _registry(models),
        )
        await w.start()
        nc = await connect(broker.url)
        agg = Aggregator(nc, scrape_interval_s=0.5)
        await agg.start(scrape_loop=False)
        gw = Gateway(nc, port=0, chat_timeout_s=50.0)
        await gw.start()

        await _wait_for(
            lambda: agg.live_workers() == ["w-eff"]
            and gw.ident in agg._scrape_targets()
            and len(gw.router.members()) == 1,
            what="worker + gateway advertising",
        )
        # the gateway advert is a scrape target but never a worker
        assert gw.ident not in agg.live_workers()
        assert [m.worker_id for m in gw.router.members()] == ["w-eff"]

        reader, writer = await asyncio.open_connection("127.0.0.1", gw.port)
        try:
            await _send(
                writer, "POST", "/v1/chat/completions",
                {"model": MID, "max_tokens": 6, "temperature": 0.0,
                 "messages": [{"role": "user", "content": "efficiency"}]},
            )
            status, _, resp = await _read_response(reader)
        finally:
            writer.close()
        assert status == 200, resp

        await agg.scrape_once()
        text = agg.render_cluster()
        check_prom_exposition(text)
        assert 'lmstudio_device_ms_total{category="served"' in text
        assert "lmstudio_goodput_tokens_per_device_s" in text
        assert "lmstudio_device_tokens_total{" in text
        assert "lmstudio_mfu" not in text and "lmstudio_program_flops_total" not in text
        assert "lmstudio_hbm_drift_bytes" in text
        # gateway families folded into the same cluster view
        assert "lmstudio_gateway_requests_total" in text
        # the gateway advert did not inflate the worker count
        assert "lmstudio_cluster_workers 1" in text
    finally:
        for x in (agg, gw):
            if x is not None:
                await x.stop()
        if w is not None:
            await w.drain()
        if nc is not None:
            await nc.close()
        await broker.stop()
