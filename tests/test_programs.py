"""The program table (serve/programs.py): built with no batcher, under the
names dispatches are recorded by, importing nothing of the scheduler; and the
one seam every dispatch crosses (``ContinuousBatcher._timed``), which builds a
shape once and feeds ``lmstudio_program_ms``."""

import ast
import asyncio
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from nats_llm_studio_tpu.config import WorkerConfig
from nats_llm_studio_tpu.engine.sampling import sample_rows
from nats_llm_studio_tpu.models.config import ModelConfig
from nats_llm_studio_tpu.models.llama import init_params
from nats_llm_studio_tpu.serve import Worker
from nats_llm_studio_tpu.serve.batcher import ContinuousBatcher
from nats_llm_studio_tpu.serve.programs import build_programs
from nats_llm_studio_tpu.serve.registry import LocalRegistry
from nats_llm_studio_tpu.store import ModelStore
from nats_llm_studio_tpu.transport import EmbeddedBroker, connect

from conftest import async_test
from test_serve_e2e import build_tiny_gguf

ROOT = Path(__file__).resolve().parent.parent

RING = {"prefill1", "prefill_full", "write_prefix_block", "admit_fused", "admit_many_fused",
        "finish_admit", "prefill_chunk_group", "select_end", "take_rows", "finish_admit_group",
        "decode", "decode_pos", "decode_pos_ext", "spec_verify", "compact_ring"}
PAGED = {"sample_first", "admit_fused_paged", "admit_many_fused_paged", "finish_admit_paged",
         "finish_admit_group_paged", "fill_row_chunk", "decode_pos_paged",
         "decode_pos_paged_ext", "spec_verify_paged", "pool_copy_block", "decode_pallas",
         "decode_pallas_ext", "spec_verify_pallas"}


def _cfg(family: str) -> ModelConfig:
    if family == "dense":
        return ModelConfig.tiny(n_layers=2, max_seq_len=64)
    from benchmark import run

    ref = run.load_module(ROOT / "benchmark/references/mla_moe_mhc.py")
    conf = json.loads((ROOT / "benchmark/tests/rehearsal/configs/tiny-mla.json").read_text())
    return ref.model_config(conf, 64).with_(dtype="float32")


@pytest.mark.parametrize("family,paged,names", [
    ("dense", True, RING | PAGED),
    ("dense", False, RING),
    ("latent", True, RING | PAGED),
])
def test_the_table_builds_with_no_batcher(family, paged, names):
    table = build_programs(_cfg(family), None, max_seq=64, paged=paged,
                           kv_block_tokens=16 if paged else 0, sample_rows=sample_rows)
    assert set(table) == names
    # every entry is a jitted program that can be lowered on its own
    assert all(callable(getattr(fn, "lower", None)) for fn in table.values())


@pytest.mark.parametrize("family,program", [
    ("dense", "decode_pos_pallas"),
    ("latent", "decode_pos_moe"),   # the burst that reads the expert counters back
])
def test_the_family_picks_its_decode_program(family, program):
    table = build_programs(_cfg(family), None, max_seq=64, paged=True, kv_block_tokens=16,
                           sample_rows=sample_rows)
    assert table["decode_pallas"].__name__ == program
    # the names the device trace's reduction finds the programs by
    assert table["admit_many_fused_paged"].__name__ == "admit_many_fused_paged"


def test_programs_imports_nothing_of_the_scheduler():
    tree = ast.parse((ROOT / "nats_llm_studio_tpu/serve/programs.py").read_text())
    modules = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            base = "." * node.level + (node.module or "")
            modules |= {base} | {f"{base}.{a.name}".replace("..", ".") for a in node.names}
        elif isinstance(node, ast.Import):
            modules |= {a.name for a in node.names}
    banned = ("batcher", "block_pool", "prefix_cache", "obs")
    assert not [m for m in modules if set(m.split(".")) & set(banned)], sorted(modules)
    src = (ROOT / "nats_llm_studio_tpu/serve/batcher.py").read_text()
    assert "jax.jit" not in src  # no device program is defined in the scheduler


def test_a_shapes_first_dispatch_builds_it_once():
    """One trace, one lowering, one compile of a shape, all inside the call
    itself: nothing asks JAX for the program before ``_timed`` dispatches it
    (a cost probe did, and JAX then reported the shape's trace twice)."""
    cfg = _cfg("dense")
    b = ContinuousBatcher(init_params(cfg, jax.random.PRNGKey(0)), cfg, max_slots=2,
                          max_seq_len=64)
    built = []

    def on_duration(event, seconds, **kw):
        if "select_end" in str(kw.get("fun_name", "")):
            built.append(event.rsplit("/", 1)[-1])

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        args = (jnp.zeros((3, 1, 7)), jnp.ones((3, 1, 7)), jnp.asarray([True, False, True]))
        out = b._select_end(*args)
        once = ["jaxpr_trace_duration", "jaxpr_to_mlir_module_duration",
                "backend_compile_duration"]
        assert built == once, built
        b._select_end(*args)
        assert built == once  # the same shape again builds nothing
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        b.stop()
    assert out[:, 0, 0].tolist() == [1.0, 0.0, 1.0]
    assert b.stats.program_histograms()["select_end"].snapshot().count == 2


@async_test
async def test_the_exposition_names_every_dispatched_program(tmp_path):
    src = tmp_path / "tiny.gguf"
    build_tiny_gguf(src)
    store = ModelStore(tmp_path / "worker")
    store.import_file(src, "acme/tiny-programs")
    broker = await EmbeddedBroker().start()
    worker = Worker(WorkerConfig(nats_url=broker.url), LocalRegistry(store, dtype="float32"))
    await worker.start()
    nc = await connect(broker.url)
    try:
        body = {"model": "acme/tiny-programs", "max_tokens": 6, "temperature": 0.0,
                "messages": [{"role": "user", "content": "programs"}]}
        reply = await nc.request("lmstudio.chat_model", json.dumps(body).encode(), timeout=50.0)
        assert json.loads(reply.payload)["ok"]
        batcher = worker.registry.loaded_engines()["acme/tiny-programs"].batcher
        dispatched = set(batcher.stats.program_histograms())
        prom = (await nc.request("lmstudio.metrics.prom", b"", timeout=10)).payload.decode()
    finally:
        await nc.close()
        await worker.drain()
        await broker.stop()
    table = set(build_programs(batcher.cfg, None, max_seq=batcher.max_seq, paged=batcher.paged,
                               kv_block_tokens=batcher.kv_block_tokens, sample_rows=sample_rows))
    assert dispatched and dispatched <= table
    assert any("admit" in n for n in dispatched) and any("decode" in n for n in dispatched)
    in_prom = {ln.split('program="', 1)[1].split('"', 1)[0] for ln in prom.splitlines()
               if ln.startswith("lmstudio_program_ms_count")}
    assert in_prom == dispatched
    for gone in ("lmstudio_mfu", "lmstudio_mbu", "lmstudio_program_flops_total",
                 "lmstudio_program_bytes_total"):
        assert gone not in prom
    assert "lmstudio_device_ms_total" in prom  # the ledger stays


def _row_cache_args(cfg, chunk=8):
    from nats_llm_studio_tpu.models.llama import make_cache

    k1, v1 = jax.eval_shape(lambda: make_cache(cfg, 1, 64))
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    one = jax.ShapeDtypeStruct((1,), jnp.int32)
    return params, jax.ShapeDtypeStruct((1, chunk), jnp.int32), k1, v1, one, one, 16


@pytest.mark.parametrize("family,program", [
    ("dense", "prefill1"), ("latent", "prefill1"),
    ("dense", "prefill_chunk_group"),   # the group's chunk always did: the two stay alike
])
def test_a_prefill_chunk_donates_its_row_cache_pair(family, program):
    """``prefill1(params, tokens, k1, v1, start, last_pos, window)`` updates
    the pair in place: arguments 2 and 3, and no other, are the program's to
    overwrite, read from the lowered program's own aliasing (on the chip a
    donated buffer is gone; every caller rebinds ``k1, v1`` from the result)."""
    cfg = _cfg(family)
    table = build_programs(cfg, None, max_seq=64, paged=True, kv_block_tokens=16,
                           sample_rows=sample_rows)
    lowered = table[program].lower(*_row_cache_args(cfg))
    donated = [[leaf.donated for leaf in jax.tree.leaves(arg)] for arg in lowered.args_info[0]]
    assert all(donated[2]) and all(donated[3])
    assert not any(flag for i, arg in enumerate(donated) if i not in (2, 3) for flag in arg)
    # each donated leaf is aliased onto a result (or left to the compiler as a donor)
    main = next(ln for ln in lowered.as_text().splitlines() if "func.func public @main" in ln)
    n_pair = len(jax.tree.leaves(lowered.args_info[0][2:4]))
    assert main.count("tf.aliasing_output") + main.count("jax.buffer_donor") == n_pair


def _watch_prefill1(b) -> list:
    """Every pair handed to the batcher's ``prefill1``, in order."""
    pairs = []
    inner = b._prefill1

    def prefill1(params, tokens, k1, v1, *rest, **kw):
        pairs.append((k1, v1))
        return inner(params, tokens, k1, v1, *rest, **kw)

    b._prefill1 = prefill1
    return pairs


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "ring"])
@pytest.mark.parametrize("kv_quant", ["none", "int8"], ids=["bf16kv", "int8kv"])
@async_test
async def test_a_chunked_single_admit_never_touches_a_donated_pair(paged, kv_quant):
    """A fresh prompt over two chunks, then a prompt that shares its first two
    chunks (prefix hit, the cached blocks filled into a new row cache, then
    ``prefill1`` continuations, then ``harvest_prefix`` and the finish): the
    tokens are the single-stream reference's, every pair handed to
    ``prefill1`` was donated (the CPU deletes a donated buffer as the chip
    does, so a second use anywhere in the admit would have raised)."""
    from nats_llm_studio_tpu.engine.generator import Generator, SamplingParams

    chunk = 16
    cfg = ModelConfig.tiny(n_layers=2, max_seq_len=64, kv_quant=kv_quant)
    params = init_params(cfg, jax.random.PRNGKey(0))
    fresh = [(i * 7 + 3) % cfg.vocab_size for i in range(2 * chunk + 5)]
    shared = fresh[:2 * chunk] + [(i * 5 + 1) % cfg.vocab_size for i in range(chunk + 3)]
    sp = SamplingParams(temperature=0.0, max_tokens=5)
    gen = Generator(params, cfg, max_seq_len=64, buckets=[8, 16, 32, 64])
    want = {name: [t for t, _ in gen.generate(p, sp)]
            for name, p in (("fresh", fresh), ("shared", shared))}
    b = ContinuousBatcher(params, cfg, max_slots=2, max_seq_len=64, buckets=[8, 64],
                          prefill_chunk=chunk, prefix_cache_blocks=8, paged=paged,
                          kv_block_tokens=16)
    pairs = _watch_prefill1(b)
    try:
        assert [t async for t in b.submit(fresh, sp)] == want["fresh"]
        assert len(pairs) == 3           # chunks at 0, 16 and 32: no group, no full prefill
        assert [t async for t in b.submit(shared, sp)] == want["shared"]
        assert len(pairs) == 5           # the hit covers two chunks; continuations at 32, 48
        assert b.prefix_cache.counters()["hit_tokens"] == 2 * chunk
    finally:
        b.stop()
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(pairs))


@pytest.mark.parametrize("cache", [0, 64], ids=["no_prefix_cache", "prefix_cache"])
@pytest.mark.parametrize("warm", ["warm_chunk_programs", "groups_of_one_length"])
@async_test(timeout=300.0)
async def test_a_group_that_narrows_builds_no_program_once_its_widths_are_warm(warm, cache):
    """What a benchmark's sweep serves (groups of ONE length at every width,
    which never narrow), with or without ``warm_chunk_programs()`` before it,
    leaves nothing for a group of mixed lengths to build: the row takes of a
    width are built where its group program first runs, and the narrowing
    runs no operation outside the table (every program XLA builds or fetches
    is an event; the benchmark refuses one inside its window). With the prefix
    cache on, every row's chunks are harvested on the way."""
    from nats_llm_studio_tpu.engine.generator import SamplingParams

    chunk, lens = 16, (21, 40, 70, 140)
    cfg = ModelConfig.tiny(n_layers=2, max_seq_len=160)
    b = ContinuousBatcher(init_params(cfg, jax.random.PRNGKey(0)), cfg, max_slots=4,
                          max_seq_len=160, buckets=[16, 32], prefill_chunk=chunk,
                          max_group_long=4, kv_block_tokens=16, prefix_cache_blocks=cache)
    sp = SamplingParams(temperature=0.0, max_tokens=6)
    built = []
    serial = iter(range(1000))  # no two prompts share a chunk: every admit is a miss

    def on_duration(event, seconds, **kw):
        if event.endswith("backend_compile_duration"):
            built.append(str(kw.get("fun_name", "?")))

    async def group(lengths):
        async def one(n):
            k = next(serial)
            prompt = [(i * (7 + 2 * (k % 9)) + 3 * k) % 95 + 32 for i in range(n)]
            return [t async for t in b.submit(prompt, sp)]

        tasks = [asyncio.create_task(one(n)) for n in lengths]
        await asyncio.sleep(0)  # one intake, one group
        return await asyncio.gather(*tasks)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        if warm == "warm_chunk_programs":
            assert await asyncio.to_thread(b.warm_chunk_programs) > 0
            assert b._takes_built == {2, 4}
        for width in (4, 2, 1):
            for n in lens:
                await group([n] * width)
        assert b._takes_built == {2, 4} and b.stats.chunked_group_narrowings == 0
        assert "take_rows" in " ".join(built)
        del built[:]
        got = await group(lens)
        assert built == [], built
        assert b.stats.chunked_group_narrowings == 2 and all(len(t) == 6 for t in got)
        if cache:
            assert b.prefix_cache.counters()["hits"] == 0
            assert b.prefix_cache.counters()["inserted_blocks"] > 0
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        b.stop()
