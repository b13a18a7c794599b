"""Cluster-scope observability plane (ISSUE 14 tentpole).

Three layers: cross-process trace assembly (Span batches on
``lmstudio.obs.spans`` -> SpanStore -> ``lmstudio.debug.trace.<id>``),
fleet metrics aggregation (per-worker scrape -> delta-first merge ->
``lmstudio.cluster.metrics.prom``), and multi-window SLO burn-rate alerts
(``slo_burn`` on ``lmstudio.events``).

Unit coverage runs against synthetic expositions and hand-built span dicts;
the acceptance e2e drives a real two-hop disaggregated chat (HTTP gateway ->
router steering -> decode worker -> prefill worker KV pull) over the
embedded broker and asserts ONE assembled tree with consistent parent links
plus aggregator/bench p95 parity on the same scrape.
"""

import asyncio
import json
import math
import time

from nats_llm_studio_tpu.obs import (
    Aggregator,
    LogHistogram,
    PromRenderer,
    SloEvaluator,
    SpanStore,
    assemble_trace,
    bucket_pairs,
    merge,
    merge_expositions,
    new_trace_id,
    parse_span_context,
    quantile,
    span_context_value,
)

from conftest import async_test
from test_obs import check_prom_exposition

INF = math.inf


# -- delta-first histogram merge ---------------------------------------------


def test_merge_exact_on_hand_built_series():
    """Two elided cumulative series with different edges: deltas convert
    per-series first, the +Inf overflow collapses to that series' last
    finite edge, quantiles land on upper bucket edges."""
    a = [(10.0, 4.0), (100.0, 6.0), (INF, 6.0)]  # 4 in (0,10], 2 in (10,100]
    b = [(50.0, 10.0), (INF, 11.0)]  # 10 in (0,50], 1 overflow -> edge 50
    m = merge([a, b])
    assert m.count == 17.0
    # edge cum: 10 -> 4, 50 -> 15 (10 + collapsed overflow), 100 -> 17
    assert m.quantile(0.2) == 10.0
    assert m.quantile(0.5) == 50.0
    assert m.quantile(0.95) == 100.0
    want_mean = (5.0 * 4 + 55.0 * 2 + 25.0 * 10 + 50.0 * 1) / 17.0
    assert abs(m.mean - want_mean) < 1e-9
    want_var = (
        4 * (5.0 - want_mean) ** 2 + 2 * (55.0 - want_mean) ** 2
        + 10 * (25.0 - want_mean) ** 2 + 1 * (50.0 - want_mean) ** 2
    ) / 17.0
    assert abs(m.variance - want_var) < 1e-9
    assert abs(m.std - want_var ** 0.5) < 1e-9
    # single-series shorthand agrees with the merge of one
    assert quantile(a, 0.95) == merge([a]).quantile(0.95)


def test_merge_ignores_counter_resets_and_empty():
    assert merge([]).count == 0
    assert merge([]).quantile(0.95) == 0.0
    # a cumulative decrease (counter reset mid-scrape) drops, not poisons
    m = merge([[(10.0, 5.0), (100.0, 3.0), (INF, 3.0)]])
    assert m.count == 5.0
    assert m.quantile(0.99) == 10.0


def test_merge_of_rendered_expositions_matches_single_histogram():
    """Recording the same values into two per-worker histograms, rendering,
    and merging the expositions gives the identical quantile as one
    histogram holding all values — the renderers share the bucket ladder,
    elision and all."""
    values_a = [3.0, 7.0, 40.0, 900.0]
    values_b = [5.0, 5.0, 60.0, 2500.0, 2500.0]
    ha, hb, hall = LogHistogram(), LogHistogram(), LogHistogram()
    for v in values_a:
        ha.record(v)
        hall.record(v)
    for v in values_b:
        hb.record(v)
        hall.record(v)
    texts = []
    for wid, h in (("w1", ha), ("w2", hb)):
        r = PromRenderer(default_labels={"worker_id": wid})
        r.histogram("lmstudio_ttft_ms", h.snapshot(), help="ttft")
        texts.append(r.render())
    m = merge(bucket_pairs(t, "lmstudio_ttft_ms") for t in texts)
    assert m.count == len(values_a) + len(values_b)
    for q in (0.5, 0.9, 0.95, 0.99):
        # identical ladder: merged quantile == whole-population histogram
        # quantile's bucket upper edge
        one = merge([bucket_pairs(_render_one(hall), "lmstudio_ttft_ms")])
        assert m.quantile(q) == one.quantile(q), q


def _render_one(h):
    r = PromRenderer(default_labels={"worker_id": "all"})
    r.histogram("lmstudio_ttft_ms", h.snapshot(), help="ttft")
    return r.render()


def test_merged_cluster_exposition_passes_strict_checker():
    """Satellite: the merged (worker_id-dropped) exposition satisfies the
    same strict Prometheus contract the per-worker output does — one TYPE
    per family, cumulative-monotone buckets, +Inf == _count."""
    texts = []
    for wid, n in (("w1", 3), ("w2", 8)):
        h = LogHistogram()
        for i in range(n):
            h.record(10.0 * (i + 1))
        r = PromRenderer(default_labels={"worker_id": wid})
        r.counter("lmstudio_requests_total", n, help="requests")
        r.counter("lmstudio_tokens_total", n * 4,
                  labels={"model": "acme/m"}, help="tokens")
        r.gauge("lmstudio_slots_busy", n % 2, help="busy")
        r.histogram("lmstudio_ttft_ms", h.snapshot(), help="ttft")
        texts.append(r.render())
    merged = merge_expositions(texts)
    types = check_prom_exposition(merged)
    assert types["lmstudio_requests_total"] == "counter"
    assert types["lmstudio_ttft_ms"] == "histogram"
    assert 'worker_id=' not in merged  # the label the merge exists to drop
    assert "lmstudio_requests_total 11" in merged  # counters sum
    # the merged histogram holds every record from both workers
    assert merge([bucket_pairs(merged, "lmstudio_ttft_ms")]).count == 11


# -- span context + assembly -------------------------------------------------


def test_span_context_roundtrip_and_lenient_parse():
    tid, sid = new_trace_id(), "ab12cd34ef56ab78"
    value = span_context_value(tid, sid)
    assert value.startswith("00-") and value.endswith("-01")
    assert parse_span_context(value) == (tid, sid)
    for bad in (None, "", "garbage", "00-onlytrace", "00--x-01"):
        assert parse_span_context(bad) is None


def test_assemble_trace_parent_links_orphans_and_ordering():
    tid = "t" * 16

    def span(sid, parent, t0, stage="s"):
        return {"trace_id": tid, "span_id": sid, "stage": stage,
                "parent_span_id": parent, "t0": t0, "t1": t0 + 1.0}

    spans = [
        span("root", "", 1.0, "gateway.request"),
        span("late-child", "root", 3.0),
        span("early-child", "root", 2.0),
        span("grand", "early-child", 2.5),
        span("orphan", "never-arrived", 0.5),  # lost parent -> extra root
        span("self", "self", 4.0),  # self-parent cannot recurse
    ]
    tree = assemble_trace(tid, spans)
    assert tree["span_count"] == 6
    roots = tree["roots"]
    assert [r["span_id"] for r in roots] == ["orphan", "root", "self"]
    root = roots[1]
    # children sort by wall t0, causality comes from the links
    assert [c["span_id"] for c in root["children"]] == [
        "early-child", "late-child"
    ]
    assert [c["span_id"] for c in root["children"][0]["children"]] == ["grand"]


def test_span_store_bounds_and_resend_updates():
    store = SpanStore(max_traces=2, max_spans_per_trace=2)
    assert store.add({"nope": 1}) is False  # malformed -> dropped, counted
    assert store.dropped_total == 1
    assert store.add({"trace_id": "t1", "span_id": "a", "stage": "x"})
    assert store.add({"trace_id": "t1", "span_id": "b", "stage": "x"})
    assert store.add({"trace_id": "t1", "span_id": "c", "stage": "x"}) is False
    # a re-send of a known span id updates in place (retries re-emit)
    assert store.add({"trace_id": "t1", "span_id": "a", "stage": "y"})
    assert {s["stage"] for s in store.get("t1")} == {"x", "y"}
    store.add({"trace_id": "t2", "span_id": "a", "stage": "x"})
    store.add({"trace_id": "t3", "span_id": "a", "stage": "x"})
    assert len(store) == 2  # oldest-touched trace evicted
    assert store.get("t2") and store.get("t3") and not store.get("t1")


# -- SLO burn-rate evaluation ------------------------------------------------


def _sample(ttft_pairs=(), requests=0.0, sheds=0.0, failed=0.0):
    return {"ttft": list(ttft_pairs), "requests": requests,
            "sheds": sheds, "failed": failed}


def test_slo_fires_only_when_both_windows_burn():
    slo = SloEvaluator(ttft_p95_ms=100.0, window_s=60.0, fast_window_s=5.0)
    assert slo.observe(0.0, {"w": _sample()}) == []  # idle baseline
    # a 1000ms TTFT burst lands inside both windows -> 10x burn in each
    alerts = slo.observe(
        100.0, {"w": _sample(ttft_pairs=[(1000.0, 10.0), (INF, 10.0)],
                             requests=10.0)}
    )
    assert len(alerts) == 1
    a = alerts[0]
    assert a["objective"] == "ttft_p95"
    assert a["target"] == 100.0
    assert a["burn_fast"] >= 10.0 and a["burn_slow"] >= 10.0
    assert a["observed_slow"] == 1000.0
    assert a["per_worker"]["w"]["ttft_p95_ms"] == 1000.0
    assert slo.last_burns["ttft_p95"]["fast"] >= 10.0


def test_slo_idle_fast_window_burns_zero_and_gates_the_alert():
    """The burst sits only in the slow window: the fast window's deltas are
    empty (no traffic is not an SLO violation), so no page."""
    slo = SloEvaluator(ttft_p95_ms=100.0, window_s=60.0, fast_window_s=5.0)
    slo.observe(0.0, {"w": _sample()})
    bad = _sample(ttft_pairs=[(1000.0, 10.0), (INF, 10.0)], requests=10.0)
    slo._snaps.append((50.0, {"w": bad}))  # burst at t=50, no alert check
    alerts = slo.observe(100.0, {"w": bad})  # unchanged since t=50
    assert alerts == []
    assert slo.last_burns["ttft_p95"]["slow"] >= 10.0
    assert slo.last_burns["ttft_p95"]["fast"] == 0.0


def test_slo_alert_debounce_honors_min_gap():
    slo = SloEvaluator(ttft_p95_ms=100.0, window_s=60.0, fast_window_s=5.0,
                       min_alert_gap_s=5.0)
    slo.observe(0.0, {"w": _sample()})

    def burst(cum):
        return {"w": _sample(ttft_pairs=[(1000.0, cum), (INF, cum)],
                             requests=cum)}

    assert len(slo.observe(100.0, burst(10.0))) == 1
    assert slo.observe(101.0, burst(20.0)) == []  # gap 1s < 5s: debounced
    assert len(slo.observe(106.0, burst(30.0))) == 1  # gap expired


def test_slo_served_ratio_and_shed_rate_objectives():
    slo = SloEvaluator(ttft_p95_ms=1e9, window_s=60.0, fast_window_s=5.0,
                       served_ratio=0.99, shed_ratio=0.05)
    slo.observe(0.0, {"w": _sample()})
    # 100 requests, 20 shed, 10 retryable-failed: served 0.7 (30x the 1%
    # budget), shed 0.2 (4x the 5% budget) -> both alert
    alerts = slo.observe(
        100.0, {"w": _sample(requests=100.0, sheds=20.0, failed=10.0)}
    )
    by_obj = {a["objective"]: a for a in alerts}
    assert set(by_obj) == {"served_ratio", "shed_rate"}
    assert abs(by_obj["served_ratio"]["observed_slow"] - 0.7) < 1e-9
    assert abs(by_obj["shed_rate"]["observed_slow"] - 0.2) < 1e-9
    assert by_obj["served_ratio"]["per_worker"]["w"]["sheds"] == 20.0


def test_slo_counter_reset_clamps_to_zero():
    slo = SloEvaluator(ttft_p95_ms=100.0, window_s=60.0, fast_window_s=5.0)
    slo.observe(0.0, {"w": _sample(requests=500.0, sheds=400.0)})
    # the worker restarted: cumulatives fell — deltas clamp at 0, no alert
    alerts = slo.observe(100.0, {"w": _sample(requests=3.0, sheds=1.0)})
    assert alerts == []
    assert slo.last_burns["shed_rate"]["slow"] == 0.0


def test_slo_sample_from_exposition_reads_the_objective_families():
    h = LogHistogram()
    for v in (12.0, 700.0):
        h.record(v)
    r = PromRenderer(default_labels={"worker_id": "w9"})
    r.histogram("lmstudio_ttft_ms", h.snapshot(), help="ttft")
    r.counter("lmstudio_batcher_requests_total", 7, help="reqs")
    r.counter("lmstudio_batcher_shed_by_cause_total", 2,
              labels={"cause": "queue_full"}, help="sheds")
    r.counter("lmstudio_inflight_failed_retryable_total", 1, help="failed")
    s = SloEvaluator.sample_from_exposition(r.render())
    assert s["requests"] == 7.0 and s["sheds"] == 2.0 and s["failed"] == 1.0
    assert merge([s["ttft"]]).count == 2


# -- acceptance e2e: two-hop disaggregated trace + p95 parity ----------------


async def _http_get_text(port, path):
    from test_gateway import _read_head, _send

    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        await _send(writer, "GET", path)
        status, headers = await _read_head(reader)
        n = int(headers.get("content-length", "0"))
        raw = await reader.readexactly(n) if n else await reader.read()
        return status, raw.decode()
    finally:
        writer.close()


def _walk(node, out):
    out.append(node)
    for c in node["children"]:
        _walk(c, out)


@async_test
async def test_two_hop_trace_assembly_p95_parity_and_slo_e2e(tmp_path):
    """ISSUE 14 acceptance: a real disaggregated chat through the HTTP
    gateway yields ONE assembled tree on ``lmstudio.debug.trace.<id>`` with
    gateway.request -> router.attempt -> worker.serve(decode) ->
    worker.kv_pull -> worker.kv_export(prefill) parent links; the
    aggregator's cluster TTFT p95 equals this test's own delta-first merge
    of the same scrape; a deliberately impossible TTFT objective fires slo_burn on the
    events subject; the merged cluster exposition and the gateway's
    /metrics both pass the strict checker."""
    from nats_llm_studio_tpu.config import WorkerConfig
    from nats_llm_studio_tpu.gateway import Gateway
    from nats_llm_studio_tpu.serve import Worker
    from nats_llm_studio_tpu.transport import EmbeddedBroker, connect

    from test_disagg import MID, _publish_tiny, _registry
    from test_gateway import _read_response, _send

    models = tmp_path / "models"
    _publish_tiny(models)
    broker = await EmbeddedBroker().start()
    wp = wd = gw = agg = nc = None
    try:
        wp = Worker(
            WorkerConfig(nats_url=broker.url, worker_id="w-prefill",
                         worker_role="prefill",
                         cluster_advert_interval_s=0.2),
            _registry(models),
        )
        wd = Worker(
            WorkerConfig(nats_url=broker.url, worker_id="w-decode",
                         worker_role="decode",
                         cluster_advert_interval_s=0.2),
            _registry(models),
        )
        await wp.start()
        await wd.start()
        nc = await connect(broker.url)
        # the impossible TTFT target makes any real chat burn both windows
        agg = Aggregator(nc, scrape_interval_s=0.5, slo_ttft_p95_ms=0.001)
        await agg.start(scrape_loop=False)
        gw = Gateway(nc, port=0, chat_timeout_s=50.0)
        await gw.start()

        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if len(gw.router.members()) == 2 and len(agg.live_workers()) == 2:
                break
            await asyncio.sleep(0.05)
        assert len(gw.router.members()) == 2, gw.router.members()
        assert agg.live_workers() == ["w-decode", "w-prefill"]

        events = []
        got_burn = asyncio.Event()

        async def on_event(msg):
            d = json.loads(msg.payload)
            events.append(d)
            if d.get("kind") == "slo_burn":
                got_burn.set()

        ev_sub = await nc.subscribe("lmstudio.events", cb=on_event)

        await agg.scrape_once()  # baseline tick: SLO windows anchor here

        trace_id = new_trace_id()
        reader, writer = await asyncio.open_connection("127.0.0.1", gw.port)
        try:
            await _send(
                writer, "POST", "/v1/chat/completions",
                {"model": MID, "max_tokens": 8, "temperature": 0.0,
                 "messages": [{"role": "user", "content": "trace me"}]},
                headers={"X-Trace-Id": trace_id},
            )
            status, _, resp = await _read_response(reader)
        finally:
            writer.close()
        assert status == 200, resp
        assert resp["choices"][0]["message"]["content"]

        # -- assembled tree over the debug subject (the tentpole claim) ------
        tree = None
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            msg = await nc.request(
                f"lmstudio.debug.trace.{trace_id}", b"", timeout=5.0
            )
            env = json.loads(msg.payload)
            if env.get("ok") and env["data"]["span_count"] >= 5:
                tree = env["data"]
                break
            await asyncio.sleep(0.1)
        assert tree is not None, "trace never assembled to >= 5 spans"
        assert tree["trace_id"] == trace_id

        # exactly one causal root: the gateway span; every hop links under it
        assert len(tree["roots"]) == 1, [r["stage"] for r in tree["roots"]]
        root = tree["roots"][0]
        assert root["stage"] == "gateway.request"
        all_spans = []
        _walk(root, all_spans)
        assert all(s["trace_id"] == trace_id for s in all_spans)

        attempts = [c for c in root["children"]
                    if c["stage"] == "router.attempt"]
        assert attempts, [c["stage"] for c in root["children"]]
        served = next(a for a in attempts if a["attrs"]["outcome"] == "ok")
        assert served["attrs"]["worker"] == "w-decode"
        assert served["attrs"]["prefill_worker"] == "w-prefill"

        serves = [c for c in served["children"] if c["stage"] == "worker.serve"]
        assert len(serves) == 1 and serves[0]["worker_id"] == "w-decode"
        pulls = [c for c in serves[0]["children"]
                 if c["stage"] == "worker.kv_pull"]
        assert len(pulls) == 1 and pulls[0]["worker_id"] == "w-decode"
        assert pulls[0]["attrs"]["peer"] == "w-prefill"
        assert pulls[0]["attrs"]["outcome"] == "ok"
        exports = [c for c in pulls[0]["children"]
                   if c["stage"] == "worker.kv_export"]
        assert len(exports) == 1 and exports[0]["worker_id"] == "w-prefill"
        assert exports[0]["attrs"]["outcome"] == "ok"
        # parent ids are consistent, not just tree-shaped
        assert serves[0]["parent_span_id"] == served["span_id"]
        assert pulls[0]["parent_span_id"] == serves[0]["span_id"]
        assert exports[0]["parent_span_id"] == pulls[0]["span_id"]

        # -- p95 parity: aggregator vs bench's merge on the SAME scrape ------
        texts = await agg.scrape_once()
        assert set(texts) == {"w-decode", "w-prefill"}
        bench_p95 = merge(
            bucket_pairs(t, "lmstudio_ttft_ms") for t in texts.values()
        ).quantile(0.95)
        assert bench_p95 > 0.0
        cluster = agg.render_cluster()
        check_prom_exposition(cluster)
        line = next(ln for ln in cluster.splitlines()
                    if ln.startswith("lmstudio_cluster_ttft_p95_ms"))
        assert float(line.rsplit(None, 1)[1]) == round(bench_p95, 3)

        # the request/reply surface serves the identical merged view
        msg = await nc.request("lmstudio.cluster.metrics.prom", b"",
                               timeout=5.0)
        check_prom_exposition(msg.payload.decode())
        assert "lmstudio_cluster_workers 2" in msg.payload.decode()

        # -- SLO burn: the second scrape saw real TTFT >> 0.001ms ------------
        await asyncio.wait_for(got_burn.wait(), timeout=5.0)
        burn = next(e for e in events if e.get("kind") == "slo_burn")
        assert burn["objective"] == "ttft_p95"
        assert burn["burn_fast"] >= 1.0 and burn["burn_slow"] >= 1.0
        assert "w-decode" in burn["per_worker"]
        assert agg.alerts_total >= 1
        await ev_sub.unsubscribe()

        # -- gateway /metrics: the HTTP-edge families, strictly checked ------
        status, text = await _http_get_text(gw.port, "/metrics")
        assert status == 200
        types = check_prom_exposition(text)
        assert types["lmstudio_gateway_ttft_ms"] == "histogram"
        # 2: the chat POST plus this very GET (counted at accept time)
        assert 'lmstudio_gateway_requests_total{gateway="gateway"} 2' in text
        assert 'lmstudio_gateway_responses_total{gateway="gateway",status="200"} 1' in text
        assert merge(
            [bucket_pairs(text, "lmstudio_gateway_ttft_ms")]
        ).count == 1
    finally:
        if agg is not None:
            await agg.stop()
        if gw is not None:
            await gw.stop()
        if nc is not None:
            await nc.close()
        for w in (wd, wp):
            if w is not None:
                try:
                    await w.drain()
                except (ConnectionError, asyncio.TimeoutError):
                    pass
        await broker.stop()
