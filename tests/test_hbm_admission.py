"""Device-memory admission on the serving load path (VERDICT r4 missing #3).

A load that would blow the per-device HBM budget must be refused with an
honest error (or make room by evicting an IDLE engine) before touching the
device — never OOM mid-serving and take live dispatches with it. The
reference delegates this to LM Studio's loader
(/root/reference/nats_llm_studio.go:46-59); in-process it's ours.
"""

import asyncio

import jax
import pytest

from nats_llm_studio_tpu.models.config import ModelConfig
from nats_llm_studio_tpu.models.export import export_params_to_gguf
from nats_llm_studio_tpu.models.llama import init_params
from nats_llm_studio_tpu.parallel.memory import estimate_device_bytes
from nats_llm_studio_tpu.serve.api import EngineError
from nats_llm_studio_tpu.serve.registry import LocalRegistry
from nats_llm_studio_tpu.store.manager import ModelStore

from conftest import async_test
from test_serve_e2e import byte_level_tokenizer_md


def _publish(models_dir, model_id, seed):
    cfg = ModelConfig.tiny(n_layers=2, max_seq_len=64)
    params = init_params(cfg, jax.random.PRNGKey(seed))
    d = models_dir / model_id
    d.mkdir(parents=True)
    export_params_to_gguf(
        d / "m.gguf", params, cfg, name=model_id,
        tokenizer_md=byte_level_tokenizer_md(cfg.vocab_size),
    )
    return cfg


def _estimate(cfg, dtype="float32", batch=2, seq=64):
    return estimate_device_bytes(cfg, {}, batch=batch, seq_len=seq)["total"]


@async_test
async def test_over_budget_load_refused_first_engine_serves(tmp_path, monkeypatch):
    models = tmp_path / "models"
    cfg = _publish(models, "acme/a", 1)
    _publish(models, "acme/b", 2)
    one = _estimate(cfg.with_(dtype="float32"))
    # room for one engine, not two
    monkeypatch.setenv("TPU_HBM_BUDGET_BYTES", str(int(one * 1.5)))
    reg = LocalRegistry(ModelStore(models), dtype="float32", max_batch_slots=2,
                        max_seq_len=64)
    eng_a = await reg.get_engine("acme/a")
    # keep A busy so it is not idle-evictable
    hold = asyncio.Event()
    release = asyncio.Event()

    async def occupy():
        async for chunk in eng_a.chat_stream(
            {"messages": [{"role": "user", "content": "hi"}], "max_tokens": 40,
             "temperature": 0.0}
        ):
            hold.set()
            if chunk.get("object") == "chat.completion":
                break
            await asyncio.sleep(0)

    task = asyncio.create_task(occupy())
    await hold.wait()
    with pytest.raises(EngineError, match="insufficient device memory"):
        await reg.get_engine("acme/b")
    # the refusal left A serving untouched
    await task
    out = await eng_a.chat(
        {"messages": [{"role": "user", "content": "again"}], "max_tokens": 3,
         "temperature": 0.0}
    )
    assert out["usage"]["completion_tokens"] == 3
    assert reg.stats()["models_loaded"] == 1
    assert reg.stats()["hbm_committed_bytes"] > 0
    for eng in reg.loaded_engines().values():
        await eng.unload()


@async_test
async def test_idle_engine_evicted_to_fit(tmp_path, monkeypatch):
    models = tmp_path / "models"
    cfg = _publish(models, "acme/a", 1)
    _publish(models, "acme/b", 2)
    one = _estimate(cfg.with_(dtype="float32"))
    monkeypatch.setenv("TPU_HBM_BUDGET_BYTES", str(int(one * 1.5)))
    reg = LocalRegistry(ModelStore(models), dtype="float32", max_batch_slots=2,
                        max_seq_len=64)
    reg.evict_grace_s = 0.0  # tests move faster than the production grace
    eng_a = await reg.get_engine("acme/a")
    out = await eng_a.chat(
        {"messages": [{"role": "user", "content": "hi"}], "max_tokens": 2,
         "temperature": 0.0}
    )
    assert out["usage"]["completion_tokens"] == 2
    # A is idle now -> loading B evicts it instead of refusing
    eng_b = await reg.get_engine("acme/b")
    assert set(reg.loaded_engines()) == {"acme/b"}
    out = await eng_b.chat(
        {"messages": [{"role": "user", "content": "yo"}], "max_tokens": 2,
         "temperature": 0.0}
    )
    assert out["usage"]["completion_tokens"] == 2
    # A reloads on demand (evicting idle B in turn)
    eng_a2 = await reg.get_engine("acme/a")
    assert set(reg.loaded_engines()) == {"acme/a"}
    for eng in reg.loaded_engines().values():
        await eng.unload()


@async_test
async def test_recently_used_idle_engine_not_evicted(tmp_path, monkeypatch):
    """The eviction grace: an engine targeted within evict_grace_s is never
    evicted even if its batcher is momentarily idle — closes the gap where
    a client holds the engine (get_engine bumped _last_used) but has not
    submitted yet."""
    models = tmp_path / "models"
    cfg = _publish(models, "acme/a", 1)
    _publish(models, "acme/b", 2)
    one = _estimate(cfg.with_(dtype="float32"))
    monkeypatch.setenv("TPU_HBM_BUDGET_BYTES", str(int(one * 1.5)))
    reg = LocalRegistry(ModelStore(models), dtype="float32", max_batch_slots=2,
                        max_seq_len=64)
    reg.evict_grace_s = 60.0  # nothing in this test is ever past the grace
    await reg.get_engine("acme/a")  # idle but freshly targeted
    with pytest.raises(EngineError, match="insufficient device memory"):
        await reg.get_engine("acme/b")
    assert set(reg.loaded_engines()) == {"acme/a"}
    for eng in reg.loaded_engines().values():
        await eng.unload()


@async_test
async def test_failed_load_releases_hbm_reservation(tmp_path, monkeypatch):
    """A load that reserves budget but then fails (corrupt file, device
    OOM) must release the reservation — a phantom commitment would refuse
    every later load until restart."""
    models = tmp_path / "models"
    cfg = _publish(models, "acme/a", 1)
    _publish(models, "acme/b", 2)
    one = _estimate(cfg.with_(dtype="float32"))
    monkeypatch.setenv("TPU_HBM_BUDGET_BYTES", str(int(one * 3)))
    reg = LocalRegistry(ModelStore(models), dtype="float32", max_batch_slots=2,
                        max_seq_len=64)
    await reg.get_engine("acme/a")
    committed = reg.stats()["hbm_committed_bytes"]
    assert committed > 0

    def boom(*a, **k):
        raise RuntimeError("simulated device OOM during load")

    monkeypatch.setattr(reg, "_load", boom)
    with pytest.raises(RuntimeError, match="simulated"):
        await reg.get_engine("acme/b")
    assert reg.stats()["hbm_committed_bytes"] == committed  # no phantom bytes
    monkeypatch.undo()
    for eng in reg.loaded_engines().values():
        await eng.unload()


@async_test
async def test_load_outlives_the_caller_that_gave_up(tmp_path, monkeypatch):
    """A caller whose deadline fires while its model loads is cancelled out
    of the wait, not out of the load: the load finishes once, the engine is
    registered, and a retry joins it instead of starting a second load next
    to the first (on a chip: a second 8 GB tree beside the one in flight)."""
    import threading

    models = tmp_path / "models"
    _publish(models, "acme/a", 1)
    reg = LocalRegistry(ModelStore(models), dtype="float32", max_batch_slots=2,
                        max_seq_len=64)
    real_load, loads, gate = reg._load, [], threading.Event()

    def slow_load(*a, **k):
        loads.append(a[0])
        gate.wait(30.0)
        return real_load(*a, **k)

    monkeypatch.setattr(reg, "_load", slow_load)
    with pytest.raises(asyncio.TimeoutError):
        await asyncio.wait_for(reg.get_engine("acme/a"), timeout=0.2)
    retry = asyncio.ensure_future(reg.get_engine("acme/a"))
    await asyncio.sleep(0.1)
    gate.set()
    eng = await asyncio.wait_for(retry, timeout=120.0)
    assert loads == ["acme/a"]  # one load, joined by the retry
    assert reg.loaded_engines() == {"acme/a": eng}
    assert not reg._loading
    await eng.unload()


@async_test
async def test_no_budget_known_means_no_check(tmp_path, monkeypatch):
    """CPU backends without memory stats (and no env override) skip
    admission — loads behave exactly as before."""
    models = tmp_path / "models"
    _publish(models, "acme/a", 1)
    _publish(models, "acme/b", 2)
    monkeypatch.delenv("TPU_HBM_BUDGET_BYTES", raising=False)
    reg = LocalRegistry(ModelStore(models), dtype="float32", max_batch_slots=2,
                        max_seq_len=64)
    await reg.get_engine("acme/a")
    await reg.get_engine("acme/b")
    assert set(reg.loaded_engines()) == {"acme/a", "acme/b"}
    for eng in reg.loaded_engines().values():
        await eng.unload()


@async_test
async def test_warm_on_load_smoke(tmp_path, monkeypatch):
    """TPU_WARM_ON_LOAD=1 pre-compiles the chunk/full-prefill programs at
    load time (instead of on the first unlucky long request) and must not
    break serving."""
    models = tmp_path / "models"
    _publish(models, "acme/a", 1)
    monkeypatch.delenv("TPU_HBM_BUDGET_BYTES", raising=False)
    monkeypatch.setenv("TPU_WARM_ON_LOAD", "1")
    reg = LocalRegistry(ModelStore(models), dtype="float32", max_batch_slots=2,
                        max_seq_len=64)
    eng = await reg.get_engine("acme/a")
    out = await eng.chat(
        {"messages": [{"role": "user", "content": "hi"}], "max_tokens": 3,
         "temperature": 0.0}
    )
    assert out["usage"]["completion_tokens"] == 3
    await eng.unload()
