"""The span primitive (obs/spans.py), the spans the batcher's owner loop and
the worker's reply path open with it, the build ledger
(obs/compile_cache.py), and the benchmark's readers of all three."""

import asyncio
import importlib.util
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from nats_llm_studio_tpu.config import WorkerConfig
from nats_llm_studio_tpu.engine.generator import SamplingParams
from nats_llm_studio_tpu.models.config import ModelConfig
from nats_llm_studio_tpu.models.llama import init_params
from nats_llm_studio_tpu.obs import compile_cache, spans
from nats_llm_studio_tpu.serve import Worker
from nats_llm_studio_tpu.serve import batcher as batcher_mod
from nats_llm_studio_tpu.serve.batcher import ContinuousBatcher
from nats_llm_studio_tpu.serve.registry import LocalRegistry
from nats_llm_studio_tpu.store import ModelStore
from nats_llm_studio_tpu.transport import EmbeddedBroker, connect

from conftest import async_test
from test_serve_e2e import build_tiny_gguf

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

OWNER_SPANS = ("batcher.intake", "batcher.tick", "batcher.admit", "batcher.dispatch",
               "batcher.readback", "batcher.deliver")


async def _stream_chat(nc, model: str, text: str, max_tokens: int) -> int:
    chunks = 0
    body = {"model": model, "stream": True, "max_tokens": max_tokens, "temperature": 0.0,
            "messages": [{"role": "user", "content": text}]}
    async for msg in nc.request_stream("lmstudio.chat_model", json.dumps(body).encode(),
                                       timeout=50.0):
        if (msg.headers or {}).get("Nats-Stream-Done"):
            assert json.loads(msg.payload)["ok"]
            break
        chunks += 1
    return chunks


async def _settled(batcher, timeout: float = 20.0) -> None:
    """Wait until the owner thread holds nothing (no slot, no waiter: it then
    blocks on its inbox and opens no span) and the ring has stopped growing.
    A reply reaches the client BEFORE the spans around it close: the burst the
    depth-2 pipeline had in flight when the stream ended is still read back
    and delivered, and the last ``worker.publish`` ends after its message is
    out. On a loaded machine that is tens of milliseconds."""
    end, seen = time.monotonic() + timeout, -1
    while time.monotonic() < end:
        await asyncio.sleep(0.05)
        n = len(spans.records())
        if batcher.idle and n == seen:
            return
        seen = n
    raise AssertionError("the owner thread did not come to rest")


@async_test
async def test_one_streamed_request_records_every_span_in_loop_order(tmp_path):
    src = tmp_path / "tiny.gguf"
    build_tiny_gguf(src)
    store = ModelStore(tmp_path / "worker")
    store.import_file(src, "acme/tiny-spans")
    broker = await EmbeddedBroker().start()
    worker = Worker(WorkerConfig(nats_url=broker.url), LocalRegistry(store, dtype="float32"))
    await worker.start()
    nc = await connect(broker.url)
    try:
        await _stream_chat(nc, "acme/tiny-spans", "warm", 3)  # load + compile
        batcher = worker.registry.loaded_engines()["acme/tiny-spans"].batcher
        await _settled(batcher)  # the warm request's last spans are in the ring
        spans.clear()
        chunks = await _stream_chat(nc, "acme/tiny-spans", "stream me", 12)
        await _settled(batcher)  # ... and this request's
        recs = spans.records()
    finally:
        await nc.close()
        await worker.drain()
        await broker.stop()
    assert all(t1 >= t0 for _, t0, t1, _ in recs)
    first = {}
    for name, t0, _, _ in recs:
        first.setdefault(name, t0)
    assert set(OWNER_SPANS) | {"worker.publish"} <= set(first), sorted(first)
    assert set(first) <= set(spans.SPAN_NAMES)
    # the owner loop: intake, tick, admit, then bursts; a readback is
    # delivered before its chunk is published
    assert first["batcher.intake"] <= first["batcher.tick"] <= first["batcher.admit"]
    assert first["batcher.admit"] <= min(first["batcher.dispatch"], first["batcher.readback"])
    assert first["batcher.readback"] <= first["batcher.deliver"] <= first["worker.publish"]
    by_name = {n: [r for r in recs if r[0] == n] for n in first}
    assert by_name["batcher.admit"][0][3]["path"] == "one"
    dispatch = by_name["batcher.dispatch"][0][3]
    assert dispatch["program"] == "decode" and dispatch["rows"] == 1 and dispatch["steps"] >= 1
    assert {r[3]["program"] for r in by_name["batcher.readback"]} >= {"admit", "decode"}
    assert sum(r[3]["tokens"] for r in by_name["batcher.deliver"]) == 12
    published = by_name["worker.publish"]
    assert len(published) == chunks
    assert sum(r[3]["tokens"] for r in published) == 12
    assert all(0.0 <= r[3]["lag_ms"] < 10_000.0 for r in published)
    # per burst, per admit, per chunk: nothing fires per token
    assert len(by_name["batcher.dispatch"]) <= 12
    assert len(by_name["batcher.deliver"]) == len(by_name["batcher.readback"])


def test_ring_is_bounded_and_filters_by_window():
    spans.clear()
    for i in range(spans.RING_SIZE + 10):
        spans.record("batcher.tick", float(i), float(i) + 0.5)
    assert len(spans.records()) == spans.RING_SIZE
    assert spans.records()[0][1] == 10.0  # the oldest fell out
    got = spans.records(100.0, 102.0, "batcher.tick")
    assert [r[1] for r in got] == [100.0, 101.0, 102.0]  # overlaps count (99.5 ends at 100.0)
    assert spans.records(name="worker.publish") == []
    spans.clear()


@pytest.mark.parametrize("annotated", [True, False])
def test_span_without_a_profiler_session_records_and_raises_nothing(annotated):
    before = spans._annotation
    spans.use_annotation(jax.profiler.TraceAnnotation if annotated else None)
    try:
        spans.clear()
        with spans.span("batcher.dispatch", program="decode") as sp:
            sp.attrs["steps"] = 8
        with pytest.raises(KeyError):
            with spans.span("batcher.tick"):
                raise KeyError("the block's own error passes through")
        (n1, a0, a1, attrs1), (n2, b0, b1, attrs2) = spans.records()
        assert (n1, attrs1) == ("batcher.dispatch", {"program": "decode", "steps": 8})
        assert (n2, attrs2) == ("batcher.tick", None)
        assert a0 <= a1 <= b0 <= b1
    finally:
        spans.use_annotation(before)
        spans.clear()


def test_build_ledger_counts_one_program_once():
    compile_cache.install_compile_cache_listener()

    @jax.jit
    def ledger_probe_program(x):
        return jnp.tanh(x) * 3.0 + 1.0

    def mine() -> dict:
        rows = [r for r in compile_cache.build_ledger(top=10_000)["programs"]
                if r[0] == "ledger_probe_program"]
        return rows[0][2] if rows else {}

    assert mine() == {}
    total0 = compile_cache.build_ledger()["total_s"]
    t_before = time.perf_counter()
    ledger_probe_program(jnp.ones((4,), jnp.float32)).block_until_ready()
    once = mine()
    assert set(once) >= {"trace", "lower", "compile"} and all(v > 0.0 for v in once.values())
    total1 = compile_cache.build_ledger()["total_s"]
    assert total1 >= total0 + sum(once[k] for k in ("trace", "lower", "compile")) - 1e-9
    ledger_probe_program(jnp.ones((4,), jnp.float32)).block_until_ready()
    assert mine() == once  # a second call of the same shape builds nothing
    # the ledger as it stood before the program existed
    then = compile_cache.build_ledger(until=t_before, top=10_000)
    assert then["total_s"] <= total1 - sum(once[k] for k in ("trace", "lower", "compile")) + 1e-6
    assert set(then["seconds"]) == {"trace", "lower", "compile", "cache_load"}


# -- the benchmark's readers --------------------------------------------------

def _reader(name: str):
    path = ROOT / "benchmark" / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"reader_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _recorded_planes() -> dict:
    """The benchmark's recorded chip trace, with the decode kernel under its
    fixed name and one owner-thread span laid over the first 30 ms."""
    planes = json.loads((ROOT / "benchmark" / "fixtures" / "trace_planes.json").read_text())
    out = {p: {l: [(n.replace("%closed_call.19 ", "%paged_decode_attention.10 "), s, d)
                   for n, s, d in evs] for l, evs in lines.items()}
           for p, lines in planes.items()}
    out["/host:CPU"]["python3"].append(("batcher.readback", 0, 30_000_000))
    return out


# what the ring held in a window of 10 s: two bursts 0.6 s apart, the owner
# thread blocked 0.5 s on each readback, two chunks published
RECORDED_SPANS = [
    ("batcher.intake", 99.0, 100.2, {"items": 1}),      # straddles the window's start
    ("batcher.dispatch", 100.3, 100.31, {"program": "decode", "rows": 8, "steps": 8}),
    ("batcher.readback", 100.31, 100.81, {"program": "decode"}),
    ("batcher.deliver", 100.81, 100.82, {"tokens": 64}),
    ("worker.publish", 100.82, 100.821, {"tokens": 8, "lag_ms": 4.0}),
    ("batcher.dispatch", 100.9, 100.91, {"program": "decode", "rows": 8, "steps": 8}),
    ("batcher.readback", 100.91, 101.41, {"program": "decode"}),
    ("worker.publish", 101.42, 101.43, {"tokens": 8, "lag_ms": 12.0}),
    ("worker.publish", 120.0, 120.1, {"tokens": 8, "lag_ms": 900.0}),  # after the window
]
RECORDED_LEDGER = {"seconds": {"trace": 9.0, "lower": 14.0, "compile": 21.0, "cache_load": 18.0},
                   "total_s": 44.0, "hits": 153, "misses": 0, "requests": 154, "programs": []}
TRACE_PROGRAMS = {"decode_pos_pallas": {"launches": 2, "seconds": 0.1},
                  "prefill1": {"launches": 1, "seconds": 0.05}}

READERS = {
    "chunk_publish_lag_p95_ms": 12.0,
    "burst_period_p50_ms": pytest.approx(600.0),
    "owner_wait_share": pytest.approx(100.0 * (0.2 + 0.5 + 0.5) / 10.0),
    "idle_unnamed_ms_per_s": None,  # a number, checked against the fixture below
    "setup_build_s": 44.0,
    "setup_cache_misses": 1,
    "attn_kernel_ms_per_step": None,
}


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_reads_a_recorded_fixture_and_nothing_from_an_empty_one(name):
    reader = _reader(name)
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert reader.METRIC["name"] == name
    listed = [m for m in manifest["per_layer"] if m["name"] == name]
    assert not listed or {k: v for k, v in listed[0].items() if k != "workloads"} == reader.METRIC
    recorded = {"window": (100.0, 110.0), "spans": RECORDED_SPANS,
                "build_ledger": RECORDED_LEDGER, "planes": _recorded_planes(),
                "trace": {"programs": TRACE_PROGRAMS}, "engine": {"decode_burst": 8}}
    empty = {"window": (100.0, 110.0), "spans": [], "build_ledger": None, "planes": {},
             "trace": {"programs": {}}, "engine": {"decode_burst": 8}}
    got = reader.read(recorded)
    assert isinstance(got, (int, float))
    if READERS[name] is not None:
        assert got == READERS[name]
    assert reader.read(empty) is None


def test_trace_readers_against_the_recorded_chip_trace():
    expected = json.loads((ROOT / "benchmark" / "fixtures" / "trace_expected.json").read_text())
    idle_ms_per_s = 1e3 * (expected["window_s"] - expected["busy_s"]) / expected["window_s"]
    planes = _recorded_planes()
    src = {"window": (0.0, 1.0), "planes": planes, "trace": {"programs": TRACE_PROGRAMS},
           "engine": {"decode_burst": 8}}
    named = _reader("idle_unnamed_ms_per_s").read(src)
    planes["/host:CPU"]["python3"].pop()  # without the span every idle ns is unnamed
    unnamed = _reader("idle_unnamed_ms_per_s").read(src)
    assert unnamed == pytest.approx(idle_ms_per_s, rel=1e-3)
    assert 0.0 <= named < unnamed
    kernel_s = dict(map(tuple, expected["device_ops"]))["%closed_call.19 custom-call"]
    assert _reader("attn_kernel_ms_per_step").read(src) == pytest.approx(kernel_s * 1e3 / 16)


# -- stage 1 is host only -----------------------------------------------------

class _NoSpan:
    def __init__(self, name, **attrs):
        self.attrs = attrs

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


def _lowered_programs(monkeypatch, with_spans: bool) -> dict:
    """Serve one short and one chunked prompt on the tiny model and return
    the lowered text of every program the batcher dispatched."""
    texts: dict = {}

    def capture(fn):
        def run(*args, **kwargs):
            # before the call: the programs donate their inputs
            texts.setdefault((fn.__name__, batcher_mod.dispatch_shape_key(args, kwargs)),
                             fn.lower(*args, **kwargs).as_text())
            return fn(*args, **kwargs)
        return run

    build = batcher_mod.build_programs
    monkeypatch.setattr(batcher_mod, "build_programs", lambda *a, **k: {
        name: capture(fn) for name, fn in build(*a, **k).items()})
    if not with_spans:
        monkeypatch.setattr(spans, "span", _NoSpan)
        monkeypatch.setattr(spans, "record", lambda *a, **k: None)
    cfg = ModelConfig.tiny(n_layers=2, max_seq_len=64)
    params = init_params(cfg, jax.random.PRNGKey(0))
    b = ContinuousBatcher(params, cfg, max_slots=2, max_seq_len=64, buckets=[8, 64],
                          prefill_chunk=16)

    async def serve():
        sp = SamplingParams(temperature=0.0, max_tokens=5)
        try:
            for prompt in ([1, 2, 3], list(range(1, 40))):
                assert len([t async for t in b.submit(prompt, sp)]) == 5
        finally:
            b.stop()

    spans.clear()
    asyncio.run(asyncio.wait_for(serve(), timeout=120.0))
    assert bool(spans.records()) == with_spans
    spans.clear()
    return texts


def test_spans_change_no_compiled_program(monkeypatch):
    with monkeypatch.context() as m:
        with_spans = _lowered_programs(m, with_spans=True)
    with monkeypatch.context() as m:
        without = _lowered_programs(m, with_spans=False)
    names = {name for name, _ in with_spans}
    assert any("decode" in n for n in names) and any("admit" in n or "prefill" in n for n in names)
    assert with_spans.keys() == without.keys()
    assert all(with_spans[k] == without[k] for k in with_spans)
