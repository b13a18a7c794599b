"""The latent-attention family in its PLAIN form (one residual stream, one
query matrix, two shared experts: kanana-2's block) against its plain
reference (``benchmark/references/mla_moe_plain.py``) on seeded weights, at
toy size on the CPU: logits, not tokens. The served side is driven the way
the batcher drives it (``tests/test_mla_moe.py``): prefill, whole or in
chunks, into a row cache of latents, scattered into a pool through a slot's
table, then ``forward_decode_paged`` steps over a table that opens new blocks.
The chunk's attention is held to the unblocked expanded form and to the
absorbed form at a window many times the chunk, and the tree to the leaves
the configuration has."""

import hashlib
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run
from benchmark.lib import correct, weights
from nats_llm_studio_tpu.models import llama, mla_moe

ROOT = Path(__file__).resolve().parents[1]
CONF = json.loads((ROOT / "benchmark/tests/rehearsal/configs/tiny-mla-plain.json").read_text())
XING = json.loads((ROOT / "benchmark/tests/rehearsal/configs/tiny-mla.json").read_text())
REF = run.load_module(ROOT / "benchmark/references/mla_moe_plain.py")
XING_REF = run.load_module(ROOT / "benchmark/references/mla_moe_mhc.py")

T, SEQ = 16, 128            # pool block tokens; a slot's table spans SEQ
PROMPT = 40                 # not a multiple of T; 24 decoded steps open blocks 3 and 4
STEPS = 24
TABLE = [3, 5, 2, 7, 1, 4, 6, 8]
# float32 through three toy layers: the sound path agrees with the reference
# (float32 at `highest`) to ~2e-4 in a log-probability, the rounding of the
# CPU's default float32 products, so the limits sit two orders above it and
# every fault far above them. The same run in bfloat16 reads ~0.1-0.5 and
# fails them (below): the limits tell a precision apart, not only a fault.
TOY_FIRST = {"median_tol": 0.02, "token_tol": 0.05}
TOY_DECODED = {"median_tol": 0.02, "token_tol": 0.05, "gap_tol": 0.05}


def seeded(conf, ref, seq=SEQ, dtype="float32"):
    from nats_llm_studio_tpu.parallel.mesh import build_mesh

    mp = pytest.MonkeyPatch()
    mp.setattr(weights, "INIT_STD", 0.125)   # N(0, 0.02) adds nothing at d 64
    if "router" in ref.weight_gains:   # the chip's cell silences it (x0); here it is live
        mp.setattr(ref, "weight_gains", dict(ref.weight_gains, router=1.0))
    try:
        cfg = ref.model_config(conf, seq).with_(dtype=dtype)
        mesh = build_mesh({"tp": 1}, devices=jax.local_devices()[:1])
        return cfg, weights.make_seeded_params(4321, ref)(None, cfg, mesh)
    finally:
        mp.undo()


@pytest.fixture(scope="module")
def model():
    return seeded(CONF, REF)


@pytest.fixture(scope="module")
def prompt():
    return [int(t) for t in np.random.default_rng(1).integers(32, 127, size=PROMPT)]


def entry(logits) -> dict:
    lp = np.asarray(jax.nn.log_softmax(jnp.asarray(logits, jnp.float32)))

    def one(i):
        return {"token": chr(int(i)), "bytes": [int(i)], "logprob": float(lp[i])}

    return dict(one(int(np.argmax(lp))),
                top_logprobs=[one(i) for i in np.argsort(-lp)[:correct.TOP_K]])


def serve(cfg, params, prompt, n, chunks=(PROMPT,)):
    """Prefill ``prompt`` in ``chunks`` into a row cache of latents, scatter
    it into the pool through the slot's table, decode n-1 greedy tokens."""
    from nats_llm_studio_tpu.ops.kvcache import kv_pool_scatter_view, kv_pool_zeros

    tbl = jnp.asarray([TABLE], jnp.int32)
    k, v = llama.make_cache(cfg, 1, SEQ)
    fwd = jax.jit(lambda tok, k, v, start: llama.forward(
        params, cfg, tok, k, v, start, uniform_start=True))
    at = 0
    for c in chunks:
        logits, k, v = fwd(jnp.asarray([prompt[at: at + c]], jnp.int32), k, v,
                           jnp.asarray([at], jnp.int32))
        at += c
    pools = [kv_pool_zeros((1 + 2 * len(TABLE), cfg.n_layers, h, T, w), jnp.dtype(cfg.dtype))
             for h, w in cfg.kv_cache_dims()]
    vb = jnp.asarray([list(range(len(TABLE)))], jnp.int32)
    kp, vp = (kv_pool_scatter_view(p, c, tbl, vb) for p, c in zip(pools, (k, v)))
    entries = [entry(logits[0, -1])]
    step = jax.jit(lambda tok, kp, vp, pos: llama.forward_decode_paged(
        params, cfg, tok, kp, vp, tbl, pos))
    for pos in range(len(prompt), len(prompt) + n - 1):
        tok = jnp.asarray([[entries[-1]["bytes"][0]]], jnp.int32)
        logits, kp, vp = step(tok, kp, vp, jnp.asarray([pos], jnp.int32))
        entries.append(entry(logits[0, -1]))
    return entries


def check(params, prompt, entries, conf=CONF, ref=REF) -> dict:
    toks = correct.served_tokens(entries)
    want = ref.tail_logprobs(params, conf, list(prompt) + toks[:-1], len(toks))
    return correct.compare_probes([(want, entries)], TOY_FIRST, TOY_DECODED)


def test_the_full_forward_is_the_reference_at_every_position(model, prompt):
    cfg, params = model
    k, v = llama.make_cache(cfg, 1, SEQ)
    logits, *_ = llama.forward(params, cfg, jnp.asarray([prompt], jnp.int32), k, v,
                               jnp.zeros((1,), jnp.int32))
    got = np.asarray(jax.nn.log_softmax(logits[0].astype(jnp.float32)))
    np.testing.assert_allclose(got, REF.tail_logprobs(params, CONF, prompt, PROMPT), atol=2e-3)
    # padded to one (T, N) as a run pads it: the same rows
    np.testing.assert_allclose(
        got[-8:], REF.tail_logprobs(params, CONF, prompt, 8, pad_to=(SEQ, 16)), atol=2e-3)


@pytest.mark.parametrize("chunks", [(PROMPT,), (17, 17, 6)], ids=["whole", "three_chunks"])
def test_prefill_then_paged_decode_steps_agree_with_the_reference(model, prompt, chunks):
    """Chunks two and three expand latents read back from the row cache; the
    decoded steps read the pool through the table (the absorbed kernel)."""
    cfg, params = model
    n = STEPS + 1 if chunks == (PROMPT,) else 4
    out = check(params, prompt, serve(cfg, params, prompt, n, chunks))
    assert out["ok"] and out["first_ok"] and out["decoded"]["ok"], out
    assert out["decoded"]["positions"] == n - 1
    assert out["decoded"]["max_abs_diff"] < 2e-3, out


def test_a_bfloat16_run_of_the_float32_configuration_fails_the_toy_limits(prompt):
    """What the tolerance is worth: the next precision down is outside it."""
    cfg, params = seeded(CONF, REF, dtype="bfloat16")
    out = check(params, prompt, serve(cfg, params, prompt, 6))
    assert not out["ok"], out


def test_the_tree_holds_the_leaves_the_configuration_has(model):
    cfg, params = model
    dense, moe = (set(params["blocks"][k]) for k in ("dense", "moe"))
    attn = {"attn_norm", "ffn_norm", "kv_norm", "wq", "w_dkv", "w_ukv", "wo"}
    assert dense == attn | {"w_gate", "w_up", "w_down"}
    assert moe == attn | {"router", "e_bias", "w_gate_e", "w_up_e", "w_down_e",
                          "w_gate_s", "w_up_s", "w_down_s"}
    assert params["blocks"]["moe"]["w_gate_s"].shape[-1] == 2 * cfg.moe_d_ff  # two shared as one
    # the Xing form keeps its own: the pair with its norm, the mixers, no wq
    xing = jax.eval_shape(lambda: mla_moe.init_params(
        XING_REF.model_config(XING, SEQ), jax.random.PRNGKey(0)))
    assert set(xing["blocks"]["dense"]) == (attn - {"wq"}) | {
        "q_norm", "w_dq", "w_uq", "w_gate", "w_up", "w_down"} | {
        f"hc_{w}_{x}" for w in ("attn", "ffn") for x in "wab"}
    # sharding rules, the memory estimate and the header follow the same leaves
    from nats_llm_studio_tpu.models.config import ModelConfig
    from nats_llm_studio_tpu.models.export import config_metadata
    from nats_llm_studio_tpu.parallel import memory
    from nats_llm_studio_tpu.parallel.mesh import build_mesh
    from nats_llm_studio_tpu.parallel.sharding import param_sharding_rules

    mesh = build_mesh({"tp": 1}, devices=jax.local_devices()[:1])
    for c, tree in ((cfg, params), (XING_REF.model_config(XING, SEQ), xing)):
        leaves = set(weights.flatten(tree))
        assert leaves - {"lm_head"} <= set(param_sharding_rules(mesh, c))
        assert {k for k in param_sharding_rules(mesh, c) if k.startswith("blocks")} <= leaves
        priced = set(memory._leaves(c, 2))
        assert priced <= leaves | {"lm_head"}, priced - leaves
        back = ModelConfig.from_gguf_metadata(config_metadata(c, "m"))
        assert (back.q_lora_rank, back.hc_mult, back.is_mla) == (c.q_lora_rank, c.hc_mult, True)
    md = config_metadata(cfg, "m")
    assert not any("hyper_connection" in k or "q_lora_rank" in k for k in md)


def _chunk_inputs(cfg, b, t, s):
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    hq, dn, dr = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    c_all = jax.random.normal(ks[2], (b, 2, 1, s, cfg.kv_lora_rank))
    r_all = jnp.pad(jax.random.normal(ks[3], (b, 2, 1, s, dr)),
                    [(0, 0)] * 4 + [(0, cfg.kv_cache_dims()[1][1] - dr)])
    p = {"w_ukv": jax.random.normal(ks[4], (cfg.kv_lora_rank, hq * (dn + cfg.v_head_dim))) * 0.1}
    return (jax.random.normal(ks[0], (b, t, hq, dn)), jax.random.normal(ks[1], (b, t, hq, dr)),
            c_all, r_all, p)


def expanded_attention(q_nope, q_rope, c_win, kr_win, p, cfg, positions):
    """The definition ``mla_moe.blocked_attention`` and the absorbed kernel are
    held to: T queries over a window of S cached tokens in the expanded form as
    ONE plane (nothing serves it: at a window of 32,768 its float32 scores are
    1 GB a row). ``positions`` [B, T]: query t sees keys at index <=
    positions[b, t]. Returns [B, T, H*dv]."""
    b, t, _, _ = q_nope.shape
    w_uk, w_uv = mla_moe._w_ukv(p, cfg)
    k_nope = jnp.einsum("bsr,rhd->bshd", c_win, w_uk)
    v = jnp.einsum("bsr,rhd->bshd", c_win, w_uv)
    key_pos = jnp.arange(c_win.shape[1], dtype=jnp.int32)
    s = jnp.einsum("bthd,bshd->bhts", q_nope, k_nope, preferred_element_type=jnp.float32)
    s = s + jnp.einsum("bthd,bsd->bhts", q_rope, kr_win, preferred_element_type=jnp.float32)
    s = jnp.where((key_pos[None, None, :] <= positions[:, :, None])[:, None],
                  s * cfg.attn_scale, jnp.float32(-1e30))
    pr = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhts,bshd->bthd", pr, v).reshape(b, t, -1)


def key_block_reads(monkeypatch, block: int, at_of=lambda at: at, seen=None):
    """``blocked_attention`` reads a block of a layer's keys with ONE kind of
    ``lax.dynamic_slice``: 5-d row cache, sizes [b, 1, 1, block, W]. Reroute
    where those reads start (``at_of``: a fault put in on purpose) and, with
    ``seen``, note the block each read started at (a visit of a block is two
    reads: its latents and its rotary keys)."""
    sound = jax.lax.dynamic_slice

    def dynamic_slice(operand, starts, sizes):
        if operand.ndim == 5 and tuple(sizes[1:4]) == (1, 1, block):
            starts = (*starts[:3], at_of(starts[3]), starts[4])
            if seen is not None:
                jax.debug.callback(lambda at: seen.append(int(at) // block), starts[3])
        return sound(operand, starts, sizes)

    monkeypatch.setattr(jax.lax, "dynamic_slice", dynamic_slice)


# starts of the rows of a group: 0, mid-block, the window's last block; the
# window (512) is many times the chunk (32) and the key block (64)
STARTS = {"one_row_at_0": [0], "one_row_mid_block": [200], "one_row_last_block": [480],
          "two_rows": [0, 333], "four_rows": [96, 0, 480, 250]}


@pytest.mark.parametrize("case", list(STARTS))
def test_the_blocked_chunk_attention_is_the_expanded_and_the_absorbed_form(case, monkeypatch):
    from nats_llm_studio_tpu.ops.mla_attention import mla_absorbed_attention

    monkeypatch.setattr(mla_moe, "_K_BLOCK", 64)
    cfg = REF.model_config(CONF, 512).with_(dtype="float32")
    starts = STARTS[case]
    b, t, s, layer = len(starts), 32, 512, 1
    q_nope, q_rope, c_all, r_all, p = _chunk_inputs(cfg, b, t, s)
    positions = jnp.asarray(starts, jnp.int32)[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
    c_win, kr_win = c_all[:, layer, 0], r_all[:, layer, 0, :, : cfg.qk_rope_head_dim]
    seen = []
    key_block_reads(monkeypatch, 64, seen=seen)
    with jax.default_matmul_precision("highest"):
        got = mla_moe.blocked_attention(q_nope, q_rope, c_all, r_all, layer, s, p, cfg, positions)
        want = expanded_attention(q_nope, q_rope, c_win, kr_win, p, cfg, positions)
        absorbed = mla_moe.absorbed_output(mla_absorbed_attention(
            mla_moe.absorbed_queries(q_nope, p, cfg), q_rope, c_win, kr_win, positions,
            cfg.attn_scale), p, cfg)
    jax.effects_barrier()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(absorbed), rtol=2e-4, atol=2e-4)
    # the loop stops at the furthest block a query of the group sees
    assert sorted(seen) == sorted(2 * list(range((max(starts) + t - 1) // 64 + 1))), seen


def _operations(text: str) -> list[str]:
    text = re.sub(r"\s*loc\((?:[^()]|\((?:[^()]|\([^()]*\))*\))*\)", "", text)
    return [l for l in text.splitlines() if l.strip() and not l.startswith("#loc")]


# sha256 of the operations of the toy Xing form's decode step as the parent
# of PR 44 lowered it (the same text on the tree before and after the plain
# form came in). A deliberate change to the Xing form's decode path records
# the new digest here and says so; the plain form must never move it.
# Recorded anew at PR 49, which changed the latent decode kernel's walk
# (``ops/mla_attention.py``) for both forms, and at PR 53, which holds the
# query product apart from its cut into heads (``ops/wquant.py flat_rows``:
# one ``optimization_barrier`` a layer, in both forms).
XING_DECODE_SHA = "b850c6396a49d51510166e6343c54b7eb7317422d77c5afcc8454785f3158fca"


def test_the_xing_forms_lowered_decode_step_is_what_it_was():
    cfg = XING_REF.model_config(XING, SEQ).with_(dtype="float32")
    params = jax.eval_shape(lambda: mla_moe.init_params(cfg, jax.random.PRNGKey(0)))
    pools = [jax.ShapeDtypeStruct((9, cfg.n_layers, h, T, w), jnp.float32)
             for h, w in cfg.kv_cache_dims()]
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    text = jax.jit(lambda p, tok, kp, vp, tbl, pos: mla_moe.forward_decode_paged(
        p, cfg, tok, kp, vp, tbl, pos)).lower(
            params, ints(2, 1), *pools, ints(2, SEQ // T), ints(2)).as_text()
    ops = _operations(text)
    assert hashlib.sha256("\n".join(ops).encode()).hexdigest() == XING_DECODE_SHA
