"""Pallas paged-decode kernel, grouped int4 weights, tp overlap (PR 17).

The decode tentpole has three coupled layers, each pinned here against the
incumbent path it replaces:

* ops/paged_attention.py — the Pallas decode kernel reads each slot's block
  table directly (no kv_pool_gather_view materialization, no pow2 window
  ladder). Greedy decode through the LIVE batcher must be token-identical
  to the XLA gather-view path on every serving shape the batcher routes:
  plain and grouped admits, chunked prefill, prefix-cache hits, int8 KVQ
  pools, speculative decode, and tp=2 across the 8 forced host devices
  (conftest.py). Off-TPU the kernel runs under the Pallas interpreter —
  same math, so the equivalence is real, just slow.
* ops/wquant.py int4 — grouped asymmetric QTensor4: round-trip error
  bounds per group size, the fused dequant-matmul against explicit
  dequantization, and end-to-end top-1 logit agreement on a random tiny
  model (the worst case for argmax stability — real checkpoints have far
  larger logit margins than noise weights).
* parallel/overlap.py — the ppermute-ring all-reduce behind TP_OVERLAP
  must keep greedy decode token-identical through the batcher (reduction
  order changes float rounding, not the argmax on these margins).

Plus the satellite knobs: DECODE_KERNEL resolution/downshift rules, the
DECODE_LADDER_RUNGS window-ladder cap, and the decode_recompiles counter.
"""

import asyncio
import os
from contextlib import contextmanager

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nats_llm_studio_tpu.engine.generator import SamplingParams
from nats_llm_studio_tpu.models.config import ModelConfig
from nats_llm_studio_tpu.models.llama import (
    ensure_lm_head,
    forward,
    init_params,
    make_cache,
)
from nats_llm_studio_tpu.ops.kvcache import quantize_rows
from nats_llm_studio_tpu.ops.paged_attention import (
    _run_blocks,
    paged_decode_attention,
    paged_decode_eligible,
)
from nats_llm_studio_tpu.ops.wquant import (
    QTensor4,
    effective_group,
    mm,
    quantize_params,
    quantize_weight4,
)
from nats_llm_studio_tpu.parallel import build_mesh
from nats_llm_studio_tpu.parallel.sharding import shard_params
from nats_llm_studio_tpu.serve.batcher import ContinuousBatcher

from conftest import async_test


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig.tiny(n_layers=2, max_seq_len=128)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@contextmanager
def _env(**kv):
    old = {k: os.environ.get(k) for k in kv}
    os.environ.update({k: str(v) for k, v in kv.items()})
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


async def _greedy_batch(params, cfg, prompts, n, kernel, mesh=None, **kw):
    """Greedy decode through a paged batcher with DECODE_KERNEL forced."""
    with _env(DECODE_KERNEL=kernel):
        b = ContinuousBatcher(params, cfg, max_slots=4, max_seq_len=64,
                              buckets=[8, 64], mesh=mesh, paged=True, **kw)
    assert b.decode_kernel == kernel, (b.decode_kernel, kernel)
    try:
        async def one(p):
            sp = SamplingParams(temperature=0.0, max_tokens=n)
            return [t async for t in b.submit(p, sp)]

        return await asyncio.gather(*[one(p) for p in prompts])
    finally:
        b.stop()


PROMPTS = [[1, 2, 3], [9, 8, 7, 6], [5], [10, 20, 30, 40, 50]]


# -- kernel equivalence through the live batcher ------------------------------


@async_test
async def test_pallas_greedy_matches_xla(model):
    """Solo + concurrent group admits: the kernel's online softmax over the
    whole table width reproduces the gather-view tokens exactly."""
    cfg, params = model
    want = await _greedy_batch(params, cfg, PROMPTS, 6, "xla")
    got = await _greedy_batch(params, cfg, PROMPTS, 6, "pallas")
    assert got == want


@async_test
async def test_pallas_kvq_greedy_matches_xla(model):
    """int8 KVQ pool: the kernel dequantizes codes in-VMEM; quantize-on-
    write must produce the same codes as the view path, so tokens match."""
    cfg, params = model
    qcfg = cfg.with_(kv_quant="int8")
    want = await _greedy_batch(params, qcfg, PROMPTS, 6, "xla")
    got = await _greedy_batch(params, qcfg, PROMPTS, 6, "pallas")
    assert got == want


@async_test
async def test_pallas_chunked_prefill_and_prefix_hit_match(model):
    """Chunked admits + a prefix-cache resend: the hit path re-enters
    decode through block tables the kernel must walk identically."""
    cfg, params = model
    prompt = [(i * 7 + 3) % cfg.vocab_size for i in range(18)]

    async def run(kernel):
        with _env(DECODE_KERNEL=kernel):
            b = ContinuousBatcher(params, cfg, max_slots=2, max_seq_len=64,
                                  buckets=[8, 64], prefill_chunk=8,
                                  prefix_cache_blocks=16, paged=True)
        try:
            sp = SamplingParams(temperature=0.0, max_tokens=6)
            first = [t async for t in b.submit(prompt, sp)]
            again = [t async for t in b.submit(prompt, sp)]
            return first, again, b.prefix_cache.counters()["hits"]
        finally:
            b.stop()

    w_first, w_again, w_hits = await run("xla")
    p_first, p_again, p_hits = await run("pallas")
    assert p_first == w_first and p_again == w_again
    assert w_hits >= 1 and p_hits >= 1


@async_test
async def test_pallas_spec_decode_matches(model):
    """spec_verify through the kernel (W = k+1 rows per step) accepts and
    emits exactly the plain greedy sequence."""
    cfg, params = model
    prompt = [7, 8, 9, 7, 8, 9, 7, 8]  # repetition: prompt-lookup drafts hit
    want = await _greedy_batch(params, cfg, [prompt], 10, "xla")
    got = await _greedy_batch(params, cfg, [prompt], 10, "pallas",
                              spec_decode_k=4)
    assert got == want


@async_test
async def test_pallas_tp2_matches_unsharded(model):
    """tp=2 on the forced host devices: the kernel runs per-shard under
    shard_map (heads split, tables replicated) and still matches the
    unsharded XLA tokens."""
    cfg, params = model
    want = await _greedy_batch(params, cfg, PROMPTS[:3], 6, "xla")
    mesh = build_mesh("tp=2", devices=jax.devices()[:2])
    sharded = shard_params(params, mesh, cfg)
    got = await _greedy_batch(sharded, cfg, PROMPTS[:3], 6, "pallas",
                              mesh=mesh)
    assert got == want


@async_test
async def test_tp_overlap_greedy_matches(model):
    """TP_OVERLAP=1: the decode projections' all-reduce rides the ppermute
    ring — different reduction order, same greedy tokens."""
    cfg, params = model
    want = await _greedy_batch(params, cfg, PROMPTS[:3], 6, "xla")
    mesh = build_mesh("tp=2", devices=jax.devices()[:2])
    sharded = shard_params(params, mesh, cfg)
    with _env(TP_OVERLAP="1"):
        got = await _greedy_batch(sharded, cfg, PROMPTS[:3], 6, "pallas",
                                  mesh=mesh)
    assert got == want


# -- the kernel's grid against a plain softmax --------------------------------


def _plain_paged_attention(q, k_pool, v_pool, tbl, pos, layer, scale):
    """Plain jax.numpy softmax over each slot's whole table in f32: gather the
    slot's blocks, mask keys past ``pos + query offset``, one softmax."""
    b, w, hq, d = q.shape
    hkv, t = k_pool.shape[2], k_pool.shape[3]
    nb = tbl.shape[1]
    f32 = jnp.float32

    def keys(pool):  # [B, NB, Hkv, T, D] -> [B, Hq, NB*T, D]
        x = pool[tbl, layer].astype(f32).transpose(0, 2, 1, 3, 4)
        return jnp.repeat(x.reshape(b, hkv, nb * t, d), hq // hkv, axis=1)

    s = jnp.einsum("bwhd,bhsd->bwhs", q.astype(f32), keys(k_pool)) * scale
    live = (jnp.arange(nb * t)[None, None, :]
            <= (pos[:, None] + jnp.arange(w)[None, :])[:, :, None])
    p = jax.nn.softmax(jnp.where(live[:, :, None, :], s, -jnp.inf), axis=-1)
    return jnp.einsum("bwhs,bhsd->bwhd", p, keys(v_pool))


GRID_CASES = {
    # name: (dtype, Hkv, group, T, table width, W, KVQ)
    "f32-hkv8": (jnp.float32, 8, 4, 16, 32, 1, False),
    "bf16-hkv8": (jnp.bfloat16, 8, 4, 16, 32, 1, False),
    "f32-hkv2-tp-shard": (jnp.float32, 2, 4, 16, 32, 1, False),
    "bf16-hkv2-tp-shard": (jnp.bfloat16, 2, 4, 16, 32, 1, False),
    "f32-hkv16": (jnp.float32, 16, 2, 16, 32, 1, False),
    "bf16-hkv16": (jnp.bfloat16, 16, 2, 16, 32, 1, False),
    "f32-spec-w7": (jnp.float32, 8, 4, 16, 32, 7, False),
    "bf16-spec-w7": (jnp.bfloat16, 8, 4, 16, 32, 7, False),
    "f32-spec-w5-hkv2": (jnp.float32, 2, 4, 16, 48, 5, False),
    "f32-kvq-t16": (jnp.float32, 8, 4, 16, 32, 1, True),
    "bf16-kvq-t16": (jnp.bfloat16, 8, 4, 16, 32, 1, True),
    "f32-kvq-t32": (jnp.float32, 8, 4, 32, 16, 1, True),
    "bf16-kvq-t32-spec-w7": (jnp.bfloat16, 8, 4, 32, 16, 7, True),
    # widths the 256-key run does not divide: k falls to a divisor
    "f32-width12-k-divides-down": (jnp.float32, 8, 4, 16, 12, 1, False),
    "bf16-width20-k-divides-down": (jnp.bfloat16, 2, 4, 16, 20, 7, False),
    "f32-kvq-width24-k-divides-down": (jnp.float32, 8, 4, 16, 24, 1, True),
    "f32-width7-prime-one-run": (jnp.float32, 8, 4, 16, 7, 1, False),
    "f32-width17-prime-k1": (jnp.float32, 8, 4, 16, 17, 1, False),
    "f32-t8-k32": (jnp.float32, 8, 4, 8, 64, 1, False),
}


def test_run_blocks_come_from_shapes():
    # 256 keys a run: 16 entries of 16 tokens, 8 of 32, and always a divisor
    assert _run_blocks(16, 128, 8, 128, 2) == 16   # the benchmark's cells
    assert _run_blocks(32, 64, 8, 128, 1) == 8
    assert _run_blocks(16, 256, 2, 128, 2) == 16   # tp=4 shard
    assert _run_blocks(16, 12, 8, 128, 4) == 12
    assert _run_blocks(16, 20, 2, 128, 2) == 10
    assert _run_blocks(16, 24, 8, 128, 1) == 12
    assert _run_blocks(16, 7, 8, 128, 4) == 7
    assert _run_blocks(16, 17, 8, 128, 4) == 1
    assert _run_blocks(8, 64, 8, 128, 4) == 32
    # landing buffers too large for the VMEM budget shorten the run, from
    # shapes alone: 16 f32 kv heads are 128 KiB a slab, 8 of them a half
    assert _run_blocks(16, 128, 16, 128, 4) == 8


@pytest.mark.parametrize("case", list(GRID_CASES))
def test_paged_kernel_grid_matches_plain_softmax(case):
    """One grid cell per slot, all kv heads inside, walking the live table
    entries in runs of k: against a plain softmax, at every edge of block,
    run and table, with an unallocated slot (null block, position 0) beside
    the live ones, so that every slot but the first finds its first run
    already started by the slot before it."""
    dtype, hkv, group, t, nb, w, kvq = GRID_CASES[case]
    d, n_layers, layer = 128, 2, 1
    k = _run_blocks(t, nb, hkv, d, 1 if kvq else jnp.dtype(dtype).itemsize)
    assert nb % k == 0
    # contexts around every edge the walk has: position 0, inside a block,
    # on a block edge, the last key of a run, the first of the next, one
    # past it, and the full table
    edges = [0, t // 2, t - 1, t, k * t - 1, k * t, k * t + 1]
    ctx = [e for e in edges if e + w <= nb * t] + [nb * t - w]
    b = len(ctx) + 1                      # the last slot is unallocated
    n_pool = (b - 1) * nb + 1             # block 0 is the null block
    rng = np.random.default_rng(sum(map(ord, case)))
    kf = rng.standard_normal((n_pool, n_layers, hkv, t, d)).astype(np.float32)
    vf = rng.standard_normal((n_pool, n_layers, hkv, t, d)).astype(np.float32)
    q = jnp.asarray(rng.standard_normal((b, w, hkv * group, d)), dtype)
    # each live slot owns a shuffled set of pool blocks: runs are scattered
    tbl = np.zeros((b, nb), np.int32)
    tbl[:-1] = 1 + rng.permutation((b - 1) * nb).reshape(b - 1, nb)
    tbl, pos = jnp.asarray(tbl), jnp.asarray(ctx + [0], jnp.int32)
    if kvq:
        k_pool, v_pool = quantize_rows(jnp.asarray(kf)), quantize_rows(jnp.asarray(vf))
        k_ref = k_pool.q.astype(jnp.float32) * k_pool.s[..., None]
        v_ref = v_pool.q.astype(jnp.float32) * v_pool.s[..., None]
    else:
        k_pool, v_pool = jnp.asarray(kf, dtype), jnp.asarray(vf, dtype)
        k_ref, v_ref = k_pool, v_pool
    scale = d ** -0.5
    got = paged_decode_attention(q, k_pool, v_pool, tbl, pos, layer, scale,
                                 interpret=True)
    want = _plain_paged_attention(q, k_ref, v_ref, tbl, pos, layer, scale)
    assert got.shape == q.shape and got.dtype == q.dtype
    got = np.asarray(got.astype(jnp.float32))
    assert np.isfinite(got).all()         # the null slot's junk is finite
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(got[:-1], np.asarray(want)[:-1], atol=tol, rtol=tol)


# -- knob resolution, ladder cap, recompile counter ---------------------------


def test_decode_kernel_resolution(model):
    cfg, params = model

    def make(paged=True, **env):
        with _env(**env):
            b = ContinuousBatcher(params, cfg, max_slots=2, max_seq_len=64,
                                  buckets=[8, 64], paged=paged)
        b.stop()
        return b.decode_kernel

    # auto off-TPU -> xla (the interpreter is for tests, not serving)
    assert make(DECODE_KERNEL="auto") == "xla"
    assert make() == make(DECODE_KERNEL="auto")
    # forced values are honored off-TPU (pallas via the interpreter)
    assert make(DECODE_KERNEL="pallas") == "pallas"
    assert make(DECODE_KERNEL="xla") == "xla"
    # the legacy contiguous layout has no kernel choice
    assert make(paged=False, DECODE_KERNEL="pallas") == "xla"
    with pytest.raises(ValueError, match="DECODE_KERNEL"):
        make(DECODE_KERNEL="mosaic")


def test_window_ladder_cap(model):
    """DECODE_LADDER_RUNGS bounds the pow2 window ladder: every bucket is
    >= the floor, so the distinct-window count (== compiled decode
    programs) is capped regardless of max_seq."""
    cfg, params = model

    def floors(rungs):
        with _env(DECODE_LADDER_RUNGS=str(rungs)):
            b = ContinuousBatcher(params, cfg, max_slots=2, max_seq_len=64,
                                  buckets=[8, 64], paged=True)
        b.stop()
        wins = {b._win_bucket(n) for n in range(1, 65)}
        return b._win_floor, wins

    floor2, wins2 = floors(2)
    assert floor2 == 32 and wins2 == {32, 64}
    floor6, wins6 = floors(6)
    assert floor6 == 8
    assert len(wins6) <= 6 and min(wins6) == 8 and max(wins6) == 64
    # every window is a pow2 (paged_window relies on T | window)
    assert all(w & (w - 1) == 0 for w in wins6)


@async_test
async def test_decode_recompile_counter(model):
    """stats.decode_recompiles counts first-seen decode program keys and
    shows up in both counters() and snapshot() (the worker exposes it as
    lmstudio_decode_recompiles_total)."""
    cfg, params = model
    with _env(DECODE_KERNEL="xla"):
        b = ContinuousBatcher(params, cfg, max_slots=4, max_seq_len=64,
                              buckets=[8, 64], paged=True)
    try:
        sp = SamplingParams(temperature=0.0, max_tokens=6)

        async def one(p):
            return [t async for t in b.submit(p, sp)]

        await asyncio.gather(*[one(list(p)) for p in PROMPTS])
        n = b.stats.decode_recompiles
        assert n >= 1
        assert n == len(b._compiled_keys)
        assert b.stats.counters()["decode_recompiles"] == n
        assert b.stats.snapshot()["decode_recompiles"] == n
        # a repeat of the same shapes compiles nothing new
        await asyncio.gather(*[one(list(p)) for p in PROMPTS])
        assert b.stats.decode_recompiles == n
    finally:
        b.stop()


def test_paged_decode_eligible_rules():
    # f32 pool: 8-row sublanes, D must tile the 128-lane axis
    assert paged_decode_eligible(16, 128, 4, False)
    assert not paged_decode_eligible(12, 128, 4, False)   # T % 8
    assert not paged_decode_eligible(16, 64, 4, False)    # D % 128
    # bf16 pool: 16-row sublanes
    assert paged_decode_eligible(16, 128, 2, False)
    assert not paged_decode_eligible(24, 128, 2, False)
    # int8 KVQ codes: the block spans the pool's whole [T, D] plane, so the
    # default 16-token block serves (tests/test_tpu_compile.py); 8 does not
    assert paged_decode_eligible(32, 128, 2, True)
    assert paged_decode_eligible(16, 128, 2, True)
    assert not paged_decode_eligible(8, 128, 2, True)
    # the shard_map heads split needs Hkv % tp == 0
    assert paged_decode_eligible(16, 128, 4, False, hkv=2, tp=2)
    assert not paged_decode_eligible(16, 128, 4, False, hkv=1, tp=2)


# -- grouped int4 quantization ------------------------------------------------


def test_int4_roundtrip_error_bounds():
    """Grouped asymmetric int4 round-trip stays inside GGUF Q4_1-class
    error, tightening as the group shrinks."""
    rng = np.random.default_rng(0)
    w = rng.standard_normal((256, 96)).astype(np.float32)
    errs = {}
    for g in (16, 32, 64):
        qt = quantize_weight4(w, group=g)
        assert qt.group == g
        deq = np.asarray(qt.dequant(jnp.float32))
        errs[g] = float(np.sqrt(np.mean((w - deq) ** 2))
                        / np.sqrt(np.mean(w ** 2)))
        assert errs[g] < 0.10, (g, errs[g])
    assert errs[16] < errs[32] < errs[64]  # finer groups -> less error
    # codes unpack to [0, 15] and the logical shape survives packing
    qt = quantize_weight4(w, group=32)
    codes = np.asarray(qt.codes())
    assert qt.shape == w.shape and codes.min() >= 0 and codes.max() <= 15


def test_int4_group_degradation_and_packing_guard():
    assert effective_group(64, 32) == 32
    assert effective_group(64, 128) == 64    # clamps to the axis
    assert effective_group(50, 32) == 10     # largest even divisor <= 32
    with pytest.raises(ValueError, match="even contraction"):
        quantize_weight4(np.zeros((7, 4), np.float32))


def test_int4_fused_matmul_matches_dequant():
    """The fused grouped dequant-matmul (_mm4, no float weight
    materialized) equals x @ dequant(w) to float tolerance."""
    rng = np.random.default_rng(1)
    w = rng.standard_normal((128, 48)).astype(np.float32)
    x = jnp.asarray(rng.standard_normal((3, 5, 128)).astype(np.float32))
    qt = jax.tree.map(jnp.asarray, quantize_weight4(w, group=32))
    want = x @ qt.dequant(jnp.float32)
    got = mm(x, qt)
    assert jnp.max(jnp.abs(got - want)) < 1e-3


@async_test
async def test_registry_int4_gguf_load(model, tmp_path):
    """quant="int4" through the registry's GGUF host path: every eligible
    leaf lands as grouped QTensor4 and the engine serves greedy tokens —
    the WQUANT=int4 knob is load-path-complete, not just an ops feature."""
    from nats_llm_studio_tpu.models.export import export_params_to_gguf
    from nats_llm_studio_tpu.serve.registry import LocalRegistry
    from nats_llm_studio_tpu.store.manager import ModelStore

    from test_serve_e2e import byte_level_tokenizer_md

    cfg, params = model
    d = tmp_path / "acme" / "int4"
    d.mkdir(parents=True)
    export_params_to_gguf(d / "m.gguf", params, cfg, name="acme/int4",
                          tokenizer_md=byte_level_tokenizer_md(cfg.vocab_size))
    reg = LocalRegistry(ModelStore(tmp_path), dtype="float32",
                        max_batch_slots=2, max_seq_len=64,
                        quant="int4", wquant_group=32)
    eng = await reg.get_engine("acme/int4")
    try:
        leaves = jax.tree.leaves(
            eng.batcher.params, is_leaf=lambda x: isinstance(x, QTensor4))
        assert sum(isinstance(x, QTensor4) for x in leaves) > 0
        out = None
        async for chunk in eng.chat_stream(
            {"messages": [{"role": "user", "content": "hi"}],
             "max_tokens": 6, "temperature": 0.0}
        ):
            if chunk.get("object") == "chat.completion":
                out = chunk
        assert out is not None
        assert out["usage"]["completion_tokens"] >= 1
    finally:
        await eng.unload()


def test_int4_top1_logit_agreement(model):
    """End-to-end: int4-quantized tiny-model logits keep top-1 agreement
    with the float reference on random weights — the worst case, since
    noise weights have near-tied logits; real checkpoints sit far above
    this floor."""
    cfg, params = model
    full = ensure_lm_head(params)
    p4 = quantize_params(full, mode="int4", group=32)
    assert any(isinstance(x, QTensor4) for x in jax.tree.leaves(
        p4, is_leaf=lambda x: isinstance(x, QTensor4)))
    tokens = jax.random.randint(jax.random.PRNGKey(7), (4, 24), 0,
                                cfg.vocab_size)
    zeros = jnp.zeros((4,), jnp.int32)
    k, v = make_cache(cfg, 4, 64)
    ref, *_ = forward(full, cfg, tokens=tokens, k_cache=k, v_cache=v,
                      start_pos=zeros)
    k, v = make_cache(cfg, 4, 64)
    got, *_ = forward(p4, cfg, tokens=tokens, k_cache=k, v_cache=v,
                      start_pos=zeros)
    agree = float(jnp.mean(jnp.argmax(ref, -1) == jnp.argmax(got, -1)))
    rel = float(jnp.sqrt(jnp.mean((ref - got) ** 2))
                / jnp.sqrt(jnp.mean(ref ** 2)))
    assert agree >= 0.7, agree
    assert rel < 0.2, rel
