"""Split GGUF (llama.cpp gguf-split layout): shard auto-detection, merged
tensor view, and model loading parity with the single-file form — the shape
70B-class public checkpoints actually ship in."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nats_llm_studio_tpu.gguf import GGUFReader, GGUFShardedReader, open_gguf
from nats_llm_studio_tpu.gguf.writer import GGUFWriter
from nats_llm_studio_tpu.models.config import ModelConfig
from nats_llm_studio_tpu.models.export import export_params_to_gguf
from nats_llm_studio_tpu.models.llama import forward, init_params, make_cache
from nats_llm_studio_tpu.parallel.loader import load_params_sharded
from nats_llm_studio_tpu.parallel.mesh import build_mesh


def load_params_from_gguf(reader, cfg):
    """The repo's one loader, onto a one-device mesh: what unsharded serving
    does (serve/registry.py)."""
    mesh = build_mesh({"tp": 1}, devices=jax.devices()[:1])
    return load_params_sharded(reader, cfg, mesh)


def _make_split(tmp_path, cfg, params, n_shards=2):
    """Re-emit a single-file export as a gguf-split shard set."""
    single = tmp_path / "model.gguf"
    export_params_to_gguf(single, params, cfg, name="tiny-split")
    with GGUFReader(single) as r:
        md = dict(r.metadata)
        names = list(r.tensors)
        arrays = {n: r.tensors[n].to_numpy().copy() for n in names}
        types = {n: r.tensors[n].ggml_type for n in names}
    per = -(-len(names) // n_shards)
    paths = []
    for i in range(n_shards):
        p = tmp_path / f"model-{i + 1:05d}-of-{n_shards:05d}.gguf"
        w = GGUFWriter(p)
        shard_md = dict(md) if i == 0 else {
            "general.architecture": md["general.architecture"]
        }
        shard_md |= {"split.no": i, "split.count": n_shards,
                     "split.tensors.count": len(names)}
        w.add_dict(shard_md)
        for n in names[i * per : (i + 1) * per]:
            w.add_tensor(n, arrays[n], types[n])
        w.write()
        paths.append(p)
    return single, paths


def test_split_auto_detect_and_parity(tmp_path):
    cfg = ModelConfig.tiny(n_layers=2)
    params = init_params(cfg, jax.random.PRNGKey(5))
    single, paths = _make_split(tmp_path, cfg, params)

    with GGUFReader(single) as ref:
        want_names = set(ref.tensors)
        cfg1 = ModelConfig.from_gguf_metadata(ref.metadata).with_(dtype="float32")
        p1 = load_params_from_gguf(ref, cfg1)

    # passing any shard path auto-discovers the siblings
    with open_gguf(paths[0]) as r:
        assert isinstance(r, GGUFShardedReader)
        assert set(r.tensors) == want_names
        cfg2 = ModelConfig.from_gguf_metadata(r.metadata).with_(dtype="float32")
        p2 = load_params_from_gguf(r, cfg2)

    tokens = jnp.asarray([[7, 8, 9, 10]], jnp.int32)
    k, v = make_cache(cfg1, 1, 16)
    a, _, _ = forward(p1, cfg1, tokens, k, v, jnp.zeros((1,), jnp.int32))
    k, v = make_cache(cfg2, 1, 16)
    b, _, _ = forward(p2, cfg2, tokens, k, v, jnp.zeros((1,), jnp.int32))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6)


def test_split_missing_shard_raises(tmp_path):
    cfg = ModelConfig.tiny(n_layers=2)
    params = init_params(cfg, jax.random.PRNGKey(5))
    _, paths = _make_split(tmp_path, cfg, params)
    paths[1].unlink()
    with pytest.raises(FileNotFoundError):
        open_gguf(paths[0])


def test_split_count_mismatch_raises(tmp_path):
    cfg = ModelConfig.tiny(n_layers=2)
    params = init_params(cfg, jax.random.PRNGKey(5))
    _, paths = _make_split(tmp_path, cfg, params, n_shards=2)
    with pytest.raises(ValueError):
        GGUFShardedReader([paths[0]])


def test_registry_loads_split_model(tmp_path):
    """LocalRegistry serves a model cached as a gguf-split shard set."""
    import sys

    sys.path.insert(0, str(__import__("pathlib").Path(__file__).parent))
    from test_serve_e2e import byte_level_tokenizer_md

    from nats_llm_studio_tpu.serve.registry import LocalRegistry
    from nats_llm_studio_tpu.store import ModelStore

    cfg = ModelConfig.tiny(vocab_size=300, n_layers=2, max_seq_len=128)
    params = init_params(cfg, jax.random.PRNGKey(6))
    # export WITH tokenizer metadata, then shard it
    single = tmp_path / "m.gguf"
    export_params_to_gguf(
        single, params, cfg, tokenizer_md=byte_level_tokenizer_md(300), name="split-e2e"
    )
    with GGUFReader(single) as r:
        md = dict(r.metadata)
        names = list(r.tensors)
        arrays = {n: r.tensors[n].to_numpy().copy() for n in names}
        types = {n: r.tensors[n].ggml_type for n in names}
    model_dir = tmp_path / "models" / "acme" / "split"
    model_dir.mkdir(parents=True)
    per = -(-len(names) // 2)
    for i in range(2):
        w = GGUFWriter(model_dir / f"m-{i + 1:05d}-of-00002.gguf")
        shard_md = dict(md) if i == 0 else {"general.architecture": md["general.architecture"]}
        shard_md |= {"split.no": i, "split.count": 2, "split.tensors.count": len(names)}
        w.add_dict(shard_md)
        for n in names[i * per : (i + 1) * per]:
            w.add_tensor(n, arrays[n], types[n])
        w.write()

    reg = LocalRegistry(ModelStore(tmp_path / "models"), dtype="float32")

    async def drive():
        eng = await reg.get_engine("acme/split")
        out = await eng.chat(
            {"model": "acme/split", "messages": [{"role": "user", "content": "hi"}],
             "max_tokens": 4, "temperature": 0.0}
        )
        assert out["usage"]["completion_tokens"] == 4
        await eng.unload()

    import asyncio

    asyncio.run(drive())


def test_publish_and_pull_split_set(tmp_path):
    """publish_model uploads every shard; pulling by model id fetches the
    whole set, so the destination cache can actually load the model."""
    import asyncio

    from nats_llm_studio_tpu.store import JetStreamStoreModule, ModelStore
    from nats_llm_studio_tpu.transport import EmbeddedBroker, connect
    from nats_llm_studio_tpu.transport.jetstream import ObjectStore

    cfg = ModelConfig.tiny(n_layers=2)
    params = init_params(cfg, jax.random.PRNGKey(5))
    src_dir = tmp_path / "src"
    src_dir.mkdir()
    _, paths = _make_split(src_dir, cfg, params)

    async def drive():
        broker = await EmbeddedBroker().start()
        JetStreamStoreModule(broker).install()
        nc = await connect(broker.url)
        objstore = ObjectStore(nc, timeout=5.0)
        ms_a = ModelStore(tmp_path / "worker_a", objstore=objstore)
        adir = ms_a.model_dir("acme/big")
        adir.mkdir(parents=True)
        for p in paths:
            (adir / p.name).write_bytes(p.read_bytes())
        await ms_a.publish_model("acme/big")
        ms_b = ModelStore(tmp_path / "worker_b", objstore=objstore)
        dest, transcript = await ms_b.pull("acme/big")
        got = sorted(f.name for f in ms_b.lookup("acme/big").files)
        assert got == sorted(p.name for p in paths), transcript
        # and the pulled set loads as one model
        with open_gguf(str(ms_b.model_dir("acme/big") / paths[0].name)) as r:
            assert isinstance(r, GGUFShardedReader)
        await nc.close()
        await broker.stop()

    asyncio.run(drive())


def test_pull_incomplete_split_set_fails_loudly(tmp_path):
    """A bucket holding only part of a shard set must fail the pull (and
    leave nothing committed in the cache) rather than cache an unloadable
    model."""
    import asyncio

    from nats_llm_studio_tpu.store import JetStreamStoreModule, ModelStore
    from nats_llm_studio_tpu.store.manager import StoreError
    from nats_llm_studio_tpu.transport import EmbeddedBroker, connect
    from nats_llm_studio_tpu.transport.jetstream import ObjectStore

    cfg = ModelConfig.tiny(n_layers=2)
    params = init_params(cfg, jax.random.PRNGKey(5))
    src_dir = tmp_path / "src"
    src_dir.mkdir()
    _, paths = _make_split(src_dir, cfg, params)

    async def drive():
        broker = await EmbeddedBroker().start()
        JetStreamStoreModule(broker).install()
        nc = await connect(broker.url)
        objstore = ObjectStore(nc, timeout=5.0)
        await objstore.ensure_bucket("llm-models")
        # only shard 1 of 2 makes it to the bucket
        await objstore.put(
            "llm-models", f"acme/big/{paths[0].name}", paths[0].read_bytes()
        )
        ms = ModelStore(tmp_path / "worker", objstore=objstore)
        with pytest.raises(StoreError, match="incomplete split set"):
            await ms.pull("acme/big")
        assert ms.lookup("acme/big") is None  # nothing committed
        await nc.close()
        await broker.stop()

    asyncio.run(drive())
