"""Model/engine tests on the JAX CPU backend (SURVEY.md §4.3): golden
consistency between prefill and incremental decode, GQA/MoE variants, GGUF
export->load roundtrip, sampling behavior."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nats_llm_studio_tpu.engine.generator import Generator, SamplingParams, default_buckets
from nats_llm_studio_tpu.engine.sampling import sample
from nats_llm_studio_tpu.gguf import GGUFReader
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


@pytest.fixture(scope="module")
def tiny():
    cfg = ModelConfig.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_forward_shapes(tiny):
    cfg, params = tiny
    k, v = make_cache(cfg, 2, 64)
    tokens = jnp.array([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    logits, k, v = forward(params, cfg, tokens, k, v, jnp.zeros((2,), jnp.int32))
    assert logits.shape == (2, 4, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert k.shape == (2, cfg.n_layers, cfg.n_kv_heads, 64, cfg.head_dim)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_unrolled_decode_matches_scan(tiny):
    """decode_unroll=True (static layer indices, view slices) must produce
    identical logits and caches to the scanned decode."""
    cfg, params = tiny
    k, v = make_cache(cfg, 2, 64)
    tokens = jnp.array([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    _, k, v = forward(params, cfg, tokens, k, v, jnp.zeros((2,), jnp.int32))
    nxt = jnp.array([[9], [10]], jnp.int32)
    pos = jnp.full((2,), 4, jnp.int32)
    want, k_w, v_w = forward(params, cfg, nxt, k, v, pos)
    cfg_u = cfg.with_(decode_unroll=True)
    got, k_g, v_g = forward(params, cfg_u, nxt, k, v, pos, attn_window=32)
    import numpy as np

    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(k_g), np.asarray(k_w), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(v_g), np.asarray(v_w), rtol=1e-6, atol=1e-6)


def test_ring_decode_matches_positional(tiny):
    """Ring decode with ring_slot == uniform position must equal positional
    decode exactly (same slots, same mask), and further ring steps must stay
    consistent with the growing sequence."""
    import numpy as np

    cfg, params = tiny
    k, v = make_cache(cfg, 2, 64)
    tokens = jnp.array([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    _, k, v = forward(params, cfg, tokens, k, v, jnp.zeros((2,), jnp.int32))
    nxt = jnp.array([[9], [10]], jnp.int32)
    pos = jnp.full((2,), 4, jnp.int32)
    want, k_w, v_w = forward(params, cfg, nxt, k, v, pos)
    got, k_g, v_g = forward(params, cfg, nxt, k, v, pos, ring_slot=jnp.int32(4))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(k_g), np.asarray(k_w), rtol=1e-6, atol=1e-6)
    # second step continues the ring
    nxt2 = jnp.array([[11], [12]], jnp.int32)
    want2, _, _ = forward(params, cfg, nxt2, k_w, v_w, pos + 1)
    got2, _, _ = forward(params, cfg, nxt2, k_g, v_g, pos + 1, ring_slot=jnp.int32(5))
    np.testing.assert_allclose(np.asarray(got2), np.asarray(want2), rtol=1e-5, atol=1e-5)


def test_ring_decode_ragged_rows_and_wrap(tiny):
    """Ragged rows sharing ring slots: each row only sees its own recent
    tokens. Build it two ways — (a) ring steps on a shared cache with rows
    of different lengths, (b) per-row dense reference — and compare."""
    import numpy as np

    cfg, params = tiny
    S = 16
    # reference: row sequence [3,1,4,1,5] decoded one by one, positional
    seq = [3, 1, 4, 1, 5, 9, 2]
    k1, v1 = make_cache(cfg, 1, S)
    logits_ref, k1, v1 = forward(
        params, cfg, jnp.asarray([seq[:3]], jnp.int32), k1, v1, jnp.zeros((1,), jnp.int32)
    )
    ref_logits = []
    for i, t in enumerate(seq[3:]):
        out, k1, v1 = forward(
            params, cfg, jnp.asarray([[t]], jnp.int32), k1, v1,
            jnp.full((1,), 3 + i, jnp.int32),
        )
        ref_logits.append(np.asarray(out[0, -1]))

    # ring: same row admitted at ring head 2 (prefix occupying wrapped slots
    # S-1, 0, 1 ... exercises wraparound), another junk row occupies slot 1
    k, v = make_cache(cfg, 2, S)
    pre_k, pre_v = k1, v1  # [1, L, Hkv, S, D] with prefix at [0..3)
    # place row 0's 3-token prefix so it ENDS at ring head 1 (slots 15,0,1)
    def place(cache, pre, row):
        c = np.array(cache)
        p = np.asarray(pre)
        c[row, :, :, 15] = p[0, :, :, 0]
        c[row, :, :, 0] = p[0, :, :, 1]
        c[row, :, :, 1] = p[0, :, :, 2]
        return jnp.asarray(c)

    k = place(k, pre_k, 0)
    v = place(v, pre_v, 0)
    pos = jnp.asarray([3, 0], jnp.int32)  # row 1 empty (anything it sees is junk)
    ring = 2
    for i, t in enumerate(seq[3:]):
        toks = jnp.asarray([[t], [7]], jnp.int32)
        out, k, v = forward(params, cfg, toks, k, v, pos, ring_slot=jnp.int32(ring))
        np.testing.assert_allclose(
            np.asarray(out[0, -1]), ref_logits[i], rtol=2e-5, atol=2e-5
        )
        pos = pos + 1
        ring = (ring + 1) % S


def test_prefill_decode_consistency(tiny):
    """The golden test: token-by-token decode must reproduce the logits of a
    single full prefill — catches cache-write, mask, and RoPE offset bugs."""
    cfg, params = tiny
    seq = [3, 14, 15, 92, 65, 35, 89]
    full = jnp.asarray([seq], jnp.int32)
    k, v = make_cache(cfg, 1, 32)
    ref_logits, _, _ = forward(params, cfg, full, k, v, jnp.zeros((1,), jnp.int32))

    # prefill 4, decode the remaining 3 one at a time
    k, v = make_cache(cfg, 1, 32)
    logits, k, v = forward(params, cfg, full[:, :4], k, v, jnp.zeros((1,), jnp.int32))
    np.testing.assert_allclose(logits[0, 3], ref_logits[0, 3], rtol=0.02, atol=5e-3)
    for t in range(4, len(seq)):
        logits, k, v = forward(
            params, cfg, full[:, t : t + 1], k, v, jnp.full((1,), t, jnp.int32)
        )
        np.testing.assert_allclose(logits[0, 0], ref_logits[0, t], rtol=0.02, atol=5e-3)


@pytest.mark.parametrize("bias", [False, True], ids=["plain", "qkv_biases"])
@pytest.mark.parametrize("program", ["prefill", "decode", "paged_step", "paged_verify"])
def test_holding_the_qkv_products_apart_is_the_identity_in_value(monkeypatch, program, bias):
    """``_qkv_rows`` keeps the split into heads out of the q / k / v products
    with an ``optimization_barrier``; the programs' logits and caches are,
    to the last bit in float32, those of the same programs without it (the
    parent of PR 52's), through ``forward`` and ``forward_decode_paged``."""
    from nats_llm_studio_tpu.models.llama import forward_decode_paged

    cfg = ModelConfig.tiny(arch="qwen2", attn_bias=True) if bias else ModelConfig.tiny()
    params = init_params(cfg, jax.random.PRNGKey(5))
    rng = np.random.default_rng(0)
    noise = lambda shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)  # noqa: E731
    if program in ("prefill", "decode"):
        t, pos = (8, [0, 0]) if program == "prefill" else (1, [5, 9])
        fn = forward
        args = [noise(c.shape) for c in make_cache(cfg, 2, 32)] + [jnp.array(pos, jnp.int32)]
    else:
        t = 1 if program == "paged_step" else 3
        fn = forward_decode_paged
        pool = (9, cfg.n_layers, cfg.n_kv_heads, 16, cfg.head_dim)
        args = [noise(pool), noise(pool), jnp.array([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32),
                jnp.array([20, 37], jnp.int32)]
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, t)), jnp.int32)

    def run():  # a new function a call: traced anew
        return jax.jit(lambda p, tok, *rest: fn(p, cfg, tok, *rest))(params, tokens, *args)

    held = run()
    passed = []
    monkeypatch.setattr(jax.lax, "optimization_barrier", lambda x: passed.append(x) or x)
    plain = run()
    assert len(passed) == 1  # one a traced layer: the scan's body
    for a, b in zip(held, plain):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_right_padded_batch_matches_unpadded(tiny):
    """Right-padded rows must produce identical logits at real positions."""
    cfg, params = tiny
    k1, v1 = make_cache(cfg, 1, 32)
    a = [7, 8, 9]
    la, _, _ = forward(params, cfg, jnp.asarray([a], jnp.int32), k1, v1, jnp.zeros((1,), jnp.int32))
    k2, v2 = make_cache(cfg, 2, 32)
    batch = jnp.asarray([a + [0, 0], [1, 2, 3, 4, 5]], jnp.int32)
    lb, _, _ = forward(params, cfg, batch, k2, v2, jnp.zeros((2,), jnp.int32))
    np.testing.assert_allclose(lb[0, : len(a)], la[0], rtol=0.02, atol=5e-3)


def test_mha_variant():
    cfg = ModelConfig.tiny(n_kv_heads=4)  # MHA: kv == q heads
    params = init_params(cfg, jax.random.PRNGKey(1))
    k, v = make_cache(cfg, 1, 16)
    logits, _, _ = forward(params, cfg, jnp.ones((1, 3), jnp.int32), k, v, jnp.zeros((1,), jnp.int32))
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_moe_forward_and_consistency():
    cfg = ModelConfig.tiny(n_experts=4, n_experts_used=2, d_ff=64)
    params = init_params(cfg, jax.random.PRNGKey(2))
    seq = [1, 2, 3, 4, 5]
    full = jnp.asarray([seq], jnp.int32)
    k, v = make_cache(cfg, 1, 16)
    ref, _, _ = forward(params, cfg, full, k, v, jnp.zeros((1,), jnp.int32))
    assert bool(jnp.all(jnp.isfinite(ref)))
    # decode consistency holds for MoE too
    k, v = make_cache(cfg, 1, 16)
    logits, k, v = forward(params, cfg, full[:, :3], k, v, jnp.zeros((1,), jnp.int32))
    for t in range(3, 5):
        logits, k, v = forward(params, cfg, full[:, t : t + 1], k, v, jnp.full((1,), t, jnp.int32))
        np.testing.assert_allclose(logits[0, 0], ref[0, t], rtol=0.02, atol=5e-3)


def test_granite_scales_change_logits(tiny):
    cfg, params = tiny
    g = cfg.with_(arch="granite", embedding_scale=2.0, residual_scale=0.5, logit_scale=0.25)
    k, v = make_cache(cfg, 1, 16)
    tokens = jnp.asarray([[1, 2, 3]], jnp.int32)
    base, _, _ = forward(params, cfg, tokens, k, v, jnp.zeros((1,), jnp.int32))
    k, v = make_cache(cfg, 1, 16)
    scaled, _, _ = forward(params, g, tokens, k, v, jnp.zeros((1,), jnp.int32))
    assert not np.allclose(base, scaled)


def test_gguf_export_load_roundtrip(tmp_path, tiny):
    cfg, params = tiny
    path = tmp_path / "tiny.gguf"
    export_params_to_gguf(path, params, cfg, name="tiny-rt")
    with GGUFReader(path) as r:
        cfg2 = ModelConfig.from_gguf_metadata(r.metadata).with_(dtype="float32")
        assert cfg2.n_layers == cfg.n_layers
        assert cfg2.n_kv_heads == cfg.n_kv_heads
        assert cfg2.head_dim == cfg.head_dim
        params2 = load_params_from_gguf(r, cfg2)
    tokens = jnp.asarray([[9, 8, 7, 6]], jnp.int32)
    k, v = make_cache(cfg, 1, 16)
    a, _, _ = forward(params, cfg, tokens, k, v, jnp.zeros((1,), jnp.int32))
    k, v = make_cache(cfg2, 1, 16)
    b, _, _ = forward(params2, cfg2, tokens, k, v, jnp.zeros((1,), jnp.int32))
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_gguf_export_load_roundtrip_moe(tmp_path):
    cfg = ModelConfig.tiny(n_experts=4, n_experts_used=2, d_ff=64)
    params = init_params(cfg, jax.random.PRNGKey(3))
    path = tmp_path / "tiny-moe.gguf"
    export_params_to_gguf(path, params, cfg, name="tiny-moe")
    with GGUFReader(path) as r:
        cfg2 = ModelConfig.from_gguf_metadata(r.metadata).with_(dtype="float32")
        assert cfg2.is_moe and cfg2.n_experts == 4
        params2 = load_params_from_gguf(r, cfg2)
    tokens = jnp.asarray([[5, 4, 3]], jnp.int32)
    k, v = make_cache(cfg, 1, 16)
    a, _, _ = forward(params, cfg, tokens, k, v, jnp.zeros((1,), jnp.int32))
    k, v = make_cache(cfg2, 1, 16)
    b, _, _ = forward(params2, cfg2, tokens, k, v, jnp.zeros((1,), jnp.int32))
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------


def test_sample_greedy():
    logits = jnp.asarray([[0.1, 5.0, 0.2, 0.3], [4.0, 0.0, 0.0, 0.0]], jnp.float32)
    out = sample(logits, jax.random.PRNGKey(0), temperature=0.0)
    assert out.tolist() == [1, 0]


def test_sample_top_p_narrow_is_greedy():
    logits = jnp.asarray([[0.0, 8.0, 1.0, 2.0]], jnp.float32)
    for seed in range(5):
        out = sample(logits, jax.random.PRNGKey(seed), temperature=1.0, top_p=0.01)
        assert out.tolist() == [1]


def test_sample_top_k_limits_support():
    logits = jnp.asarray([[1.0, 2.0, 3.0, 4.0, 5.0]], jnp.float32)
    seen = set()
    for seed in range(40):
        out = sample(logits, jax.random.PRNGKey(seed), temperature=2.0, top_k=2)
        seen.add(int(out[0]))
    assert seen <= {3, 4}
    assert len(seen) == 2  # both of the top-2 actually reachable


def test_sample_per_row_params():
    logits = jnp.tile(jnp.asarray([[0.0, 3.0, 1.0, 2.0]], jnp.float32), (2, 1))
    temp = jnp.asarray([0.0, 5.0])  # row0 greedy, row1 hot
    outs = {tuple(sample(logits, jax.random.PRNGKey(s), temperature=temp).tolist()) for s in range(30)}
    assert all(o[0] == 1 for o in outs)  # greedy row fixed
    assert len({o[1] for o in outs}) > 1  # hot row varies


# ---------------------------------------------------------------------------
# generator
# ---------------------------------------------------------------------------


def test_default_buckets():
    assert default_buckets(256, 32) == [32, 64, 128, 256]
    assert default_buckets(100, 32) == [32, 64, 100]


def test_generator_streams_and_stops(tiny):
    cfg, params = tiny
    gen = Generator(params, cfg, max_seq_len=64, buckets=[8, 16, 32, 64])
    sp = SamplingParams(temperature=0.0, max_tokens=8, seed=0)
    toks = [t for t, _ in gen.generate([1, 2, 3], sp)]
    assert 0 < len(toks) <= 8
    assert all(0 <= t < cfg.vocab_size for t in toks)
    # greedy determinism
    toks2 = [t for t, _ in gen.generate([1, 2, 3], sp)]
    assert toks == toks2


def test_generator_matches_forward_greedy(tiny):
    """Generator's bucketed prefill + fused decode must equal raw forward."""
    cfg, params = tiny
    prompt = [5, 6, 7]
    gen = Generator(params, cfg, max_seq_len=32, buckets=[4, 8, 16, 32])
    got = [t for t, _ in gen.generate(prompt, SamplingParams(temperature=0.0, max_tokens=4))]

    k, v = make_cache(cfg, 1, 32)
    ids = list(prompt)
    logits, k, v = forward(params, cfg, jnp.asarray([ids], jnp.int32), k, v, jnp.zeros((1,), jnp.int32))
    want = []
    nxt = int(jnp.argmax(logits[0, len(ids) - 1]))
    for step in range(4):
        want.append(nxt)
        logits, k, v = forward(
            params, cfg, jnp.asarray([[nxt]], jnp.int32), k, v,
            jnp.full((1,), len(ids) + step, jnp.int32),
        )
        nxt = int(jnp.argmax(logits[0, 0]))
    assert got == want


def test_generator_stop_ids(tiny):
    cfg, params = tiny
    gen = Generator(params, cfg, max_seq_len=32, buckets=[8, 32])
    # find the first greedy token, then declare it a stop id
    first = next(gen.generate([1, 2], SamplingParams(temperature=0.0, max_tokens=1)))[0]
    out = [
        t
        for t, _ in gen.generate(
            [1, 2], SamplingParams(temperature=0.0, max_tokens=8, stop_ids=frozenset({first}))
        )
    ]
    assert out == []


def test_generator_stats(tiny):
    cfg, params = tiny
    gen = Generator(params, cfg, max_seq_len=32, buckets=[8, 32])
    stats = None
    for _, stats in gen.generate([1, 2, 3, 4], SamplingParams(temperature=0.0, max_tokens=5)):
        pass
    assert stats is not None
    assert stats.prompt_tokens == 4
    assert stats.completion_tokens >= 1
    assert stats.ttft_s > 0


def test_qwen2_bias_forward_and_roundtrip(tmp_path):
    """Qwen2-family: QKV biases change the logits, survive prefill/decode
    consistency, and round-trip through GGUF (including the rope pair
    permutation applied to q/k biases)."""
    cfg = ModelConfig.tiny(arch="qwen2", n_layers=2, attn_bias=True)
    params = init_params(cfg, jax.random.PRNGKey(3))
    assert "bq" in params["blocks"]
    tokens = jnp.asarray([[5, 6, 7, 8]], jnp.int32)
    k, v = make_cache(cfg, 1, 16)
    with_bias, k, v = forward(params, cfg, tokens, k, v, jnp.zeros((1,), jnp.int32))
    # decode step must match the full 5-token prefill at the same position
    # (pins the bias path through t==1 decode, not just prefill)
    nxt, _, _ = forward(
        params, cfg, jnp.asarray([[9]], jnp.int32), k, v, jnp.full((1,), 4, jnp.int32)
    )
    k5, v5 = make_cache(cfg, 1, 16)
    full5, _, _ = forward(
        params, cfg, jnp.asarray([[5, 6, 7, 8, 9]], jnp.int32), k5, v5,
        jnp.zeros((1,), jnp.int32),
    )
    np.testing.assert_allclose(
        np.asarray(nxt[0, -1]), np.asarray(full5[0, -1]), rtol=2e-4, atol=2e-4
    )
    zeroed = dict(params)
    zeroed["blocks"] = dict(params["blocks"])
    for bk_ in ("bq", "bk", "bv"):
        zeroed["blocks"][bk_] = jnp.zeros_like(params["blocks"][bk_])
    k0, v0 = make_cache(cfg, 1, 16)
    no_bias, _, _ = forward(zeroed, cfg, tokens, k0, v0, jnp.zeros((1,), jnp.int32))
    assert not np.allclose(np.asarray(with_bias), np.asarray(no_bias))

    path = tmp_path / "qwen2.gguf"
    export_params_to_gguf(path, params, cfg, name="tiny-qwen2")
    with GGUFReader(path) as r:
        cfg2 = ModelConfig.from_gguf_metadata(r.metadata).with_(dtype="float32")
        assert cfg2.attn_bias  # derived from the architecture name
        params2 = load_params_from_gguf(r, cfg2)
    k2, v2 = make_cache(cfg2, 1, 16)
    again, _, _ = forward(params2, cfg2, tokens, k2, v2, jnp.zeros((1,), jnp.int32))
    np.testing.assert_allclose(np.asarray(again), np.asarray(with_bias), rtol=1e-5, atol=1e-5)


def test_gemma_family_forward_and_roundtrip(tmp_path):
    """Gemma-family: GELU MLP, tied embeddings with
    sqrt(d_model) embedding scaling — all derived from the arch name and
    consistent through prefill/decode and the GGUF round-trip."""
    cfg = ModelConfig.tiny(
        arch="gemma", n_layers=2, mlp_act="gelu",
        tie_embeddings=True, embedding_scale=8.0,  # sqrt(64)
    )
    params = init_params(cfg, jax.random.PRNGKey(4))
    assert "lm_head" not in params  # tied
    seq = [3, 14, 15, 9, 2, 6]
    k, v = make_cache(cfg, 1, 16)
    full, _, _ = forward(
        params, cfg, jnp.asarray([seq], jnp.int32), k, v, jnp.zeros((1,), jnp.int32)
    )
    # token-by-token decode reproduces the full prefill logits
    k, v = make_cache(cfg, 1, 16)
    _, k, v = forward(
        params, cfg, jnp.asarray([seq[:3]], jnp.int32), k, v, jnp.zeros((1,), jnp.int32)
    )
    outs = []
    for i, t in enumerate(seq[3:]):
        o, k, v = forward(
            params, cfg, jnp.asarray([[t]], jnp.int32), k, v,
            jnp.full((1,), 3 + i, jnp.int32),
        )
        outs.append(np.asarray(o[0, -1]))
    np.testing.assert_allclose(outs[-1], np.asarray(full[0, -1]), rtol=2e-4, atol=2e-4)

    path = tmp_path / "gemma.gguf"
    export_params_to_gguf(path, params, cfg, name="tiny-gemma")
    with GGUFReader(path) as r:
        cfg2 = ModelConfig.from_gguf_metadata(r.metadata).with_(dtype="float32")
        assert cfg2.mlp_act == "gelu" and not cfg2.norm_plus_one
        # (GGUF stores gemma norms with the +1 already folded in)
        assert cfg2.embedding_scale == 8.0
        params2 = load_params_from_gguf(r, cfg2)
    k2, v2 = make_cache(cfg2, 1, 16)
    again, _, _ = forward(
        params2, cfg2, jnp.asarray([seq], jnp.int32), k2, v2, jnp.zeros((1,), jnp.int32)
    )
    np.testing.assert_allclose(np.asarray(again), np.asarray(full), rtol=1e-5, atol=1e-5)


def test_unsupported_archs_rejected():
    """Architectures whose topology the model does not implement must fail
    loudly at config time, not half-run to garbage logits."""
    for arch in ("gemma2", "qwen2moe"):
        md = {"general.architecture": arch, f"{arch}.block_count": 2,
              f"{arch}.embedding_length": 64, f"{arch}.attention.head_count": 4}
        with pytest.raises(NotImplementedError):
            ModelConfig.from_gguf_metadata(md)
