"""The latent-attention / routed-expert / residual-stream family
(``models/mla_moe.py``, ``ops/mla_attention.py``) against its plain reference
(``benchmark/references/mla_moe_mhc.py``) on seeded weights, at toy size on
the CPU: logits, not tokens. The served side is driven the way the batcher
drives it: ``models.llama.forward`` prefill (whole, or in three chunks) into a
row cache of latents, scattered into a pool through a slot's table, then
``forward_decode_paged`` steps (the absorbed Pallas kernel in interpreter
mode) over a table that opens new blocks. Faults put in on purpose must each
fail the toy limits."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run
from benchmark.lib import correct, weights
from nats_llm_studio_tpu.models import experts, llama, mla_moe
from nats_llm_studio_tpu.models.config import ModelConfig
from test_mla_moe_plain import expanded_attention  # the one-plane definition

ROOT = Path(__file__).resolve().parents[1]
CONF = json.loads((ROOT / "benchmark/tests/rehearsal/configs/tiny-mla.json").read_text())
REF = run.load_module(ROOT / "benchmark/references/mla_moe_mhc.py")

T, SEQ = 16, 128            # pool block tokens; a slot's table spans SEQ
PROMPT = 40                 # not a multiple of T; 24 decoded steps open blocks 3 and 4
STEPS = 24
TABLE = [3, 5, 2, 7, 1, 4, 6, 8]
# float32 through three toy layers: the sound path agrees to ~1e-5, so the
# limits sit three orders above it and every fault far above them
TOY_FIRST = {"median_tol": 0.02, "token_tol": 0.05}
TOY_DECODED = {"median_tol": 0.02, "token_tol": 0.05, "gap_tol": 0.05}


@pytest.fixture(scope="module")
def model():
    mp = pytest.MonkeyPatch()
    mp.setattr(weights, "INIT_STD", 0.125)   # N(0, 0.02) adds nothing at d 64
    try:
        from nats_llm_studio_tpu.parallel.mesh import build_mesh

        cfg = REF.model_config(CONF, SEQ).with_(dtype="float32")
        mesh = build_mesh({"tp": 1}, devices=jax.local_devices()[:1])
        # the schema is the reference's param_shapes, the gains its
        # weight_gains, the placement the program's rule for every leaf
        yield cfg, weights.make_seeded_params(4321, REF)(None, cfg, mesh)
    finally:
        mp.undo()


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


def check(params, prompt, entries) -> dict:
    toks = correct.served_tokens(entries)
    ref = REF.tail_logprobs(params, CONF, list(prompt) + toks[:-1], len(toks))
    return correct.compare_probes([(ref, entries)], TOY_FIRST, TOY_DECODED)


def test_prefill_then_24_paged_decode_steps_agree_with_the_reference(model, prompt):
    cfg, params = model
    out = check(params, prompt, serve(cfg, params, prompt, STEPS + 1))
    assert out["ok"] and out["first_ok"] and out["decoded"]["ok"], out
    assert out["decoded"]["positions"] == STEPS
    assert out["decoded"]["max_abs_diff"] < 1e-3, out


def test_a_prompt_prefilled_in_three_chunks_agrees_with_the_reference(model, prompt):
    """Chunks two and three expand latents read back from the row cache."""
    cfg, params = model
    out = check(params, prompt, serve(cfg, params, prompt, 4, chunks=(17, 17, 6)))
    assert out["ok"] and out["first_ok"] and out["decoded"]["ok"], out


def _unshared_rotary_key(h, p, cfg, cos, sin):
    """mla_project with head h's rotary query turned h positions on: what
    the scores see if the rotary key were rotated per head, not shared."""
    q_nope, q_rope, c, kr = SOUND_PROJECT(h, p, cfg, cos, sin)
    heads = jnp.arange(cfg.n_heads, dtype=jnp.int32)[None]
    hc, hs = mla_moe.rope_tables(cfg, jnp.broadcast_to(heads, q_rope.shape[:1] + heads.shape[1:]))
    turned = jnp.swapaxes(mla_moe.apply_rope(jnp.swapaxes(q_rope, 1, 2), hc, hs), 1, 2)
    return q_nope, turned, c, kr


def _latent_before_its_norm(h, p, cfg, cos, sin):
    q_nope, q_rope, _, kr = SOUND_PROJECT(h, p, cfg, cos, sin)
    return q_nope, q_rope, mla_moe.mm(h, p["w_dkv"])[..., : cfg.kv_lora_rank], kr


SOUND_PROJECT = mla_moe.mla_project


def _zeroed(params, leaf):
    moe = dict(params["blocks"]["moe"])
    moe[leaf] = jnp.zeros_like(moe[leaf])
    return dict(params, blocks=dict(params["blocks"], moe=moe))


FAULTS = {
    "shared expert left out": dict(params=lambda p: _zeroed(p, "w_down_s")),
    "selection bias ignored": dict(params=lambda p: _zeroed(p, "e_bias")),
    "routed_scaling_factor dropped": dict(cfg=dict(routed_scaling=1.0)),
    "Sinkhorn skipped": dict(cfg=dict(hc_sinkhorn_iters=0)),
    "k_r rotated per head instead of shared": dict(project=_unshared_rotary_key),
    "YaRN left out": dict(cfg=dict(rope_factor=1.0)),
    "the latent cached before its norm": dict(project=_latent_before_its_norm),
}


@pytest.mark.parametrize("name", list(FAULTS))
def test_a_fault_put_in_on_purpose_fails_the_toy_limits(model, prompt, name, monkeypatch):
    cfg, params = model
    how = FAULTS[name]
    if "project" in how:
        monkeypatch.setattr(mla_moe, "mla_project", how["project"])
    served = serve(cfg.with_(**how.get("cfg", {})), how.get("params", lambda p: p)(params),
                   prompt, 6)
    out = check(params, prompt, served)
    d = out["decoded"]
    worst = max(d["median_abs_diff"] / d["median_tolerance"],
                d["max_abs_diff"] / d["token_tolerance"])
    assert not out["ok"] and worst > 5, (name, out)
    print(f"\n{name}: decoded median {d['median_abs_diff']:.3f}, max {d['max_abs_diff']:.3f}")


def test_every_row_on_the_same_experts_is_held_to_the_reference(model, prompt):
    """Dropless: a selection bias that sends every row to experts 0 and 1
    (any capacity factor would drop most of them) changes nothing about the
    agreement, and the decode counters say two experts, every row on each."""
    cfg, params = model
    moe = dict(params["blocks"]["moe"])
    moe["e_bias"] = jnp.zeros_like(moe["e_bias"]).at[:, :2].set(100.0)
    crowded = dict(params, blocks=dict(params["blocks"], moe=moe))
    out = check(crowded, prompt, serve(cfg.with_(moe_capacity_factor=0.01), crowded, prompt, 4))
    assert out["ok"] and out["decoded"]["max_abs_diff"] < 1e-3, out
    pools = [jnp.zeros((4, cfg.n_layers, h, T, w), jnp.float32) for h, w in cfg.kv_cache_dims()]
    tbl = jnp.asarray([[1, 0], [2, 0], [3, 0], [0, 0]], jnp.int32)   # the fourth slot is empty
    *_, stats = jax.jit(lambda: llama.forward_decode_paged(
        crowded, cfg, jnp.ones((4, 1), jnp.int32), *pools, tbl, jnp.zeros((4,), jnp.int32),
        moe_stats=True))()
    assert np.asarray(stats).tolist() == [[2, 3, 3]] * cfg.n_moe_layers


# (picks of the 8 rows [8, 4], live rows, experts the list must hold, most rows on one)
_SAME = [[3, 17, 40, 63]] * 8
_DISTINCT = (np.arange(32).reshape(8, 4) * 2 + 1).tolist()
HIT_CASES = {
    "every_row_on_the_same_four": (_SAME, [1] * 8, 4, 8),
    "every_pick_distinct": (_DISTINCT, [1] * 8, 32, 1),
    "one_live_row_of_eight": (_DISTINCT, [0, 0, 0, 1, 0, 0, 0, 0], 4, 1),
    "no_live_row": (_DISTINCT, [0] * 8, 0, 0),
    "a_dead_slots_picks_stay_out": (_SAME[:7] + [[5, 6, 7, 8]], [1] * 7 + [0], 4, 7),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(HIT_CASES))
def test_the_hit_list_is_the_dense_dispatch_over_the_experts_hit(case, dtype, monkeypatch):
    """The same layer twice on the same rows: dense dispatch (the layer's own
    expert leaves) and the hit list (the WHOLE stacks and the layer's place in
    them). Live rows agree to the order of the products; the list holds the
    experts of live rows only, so a row of an empty slot gets the shared
    expert's output plus whatever listed experts it also picked."""
    picks, live, n_hit, rows_max = HIT_CASES[case]
    cfg = REF.model_config(CONF, SEQ).with_(n_experts=64, n_experts_used=4, dtype=dtype)
    dt, d, f, e, place = jnp.dtype(dtype), cfg.d_model, cfg.moe_d_ff, cfg.n_experts, 1
    ks = iter(jax.random.split(jax.random.PRNGKey(11), 8))
    rand = lambda *shape: (jax.random.normal(next(ks), shape) * 0.3).astype(dt)  # noqa: E731
    stacks = {"w_gate_e": rand(2, e, d, f), "w_up_e": rand(2, e, d, f),
              "w_down_e": rand(2, e, f, d)}
    p = {"w_gate_s": rand(d, f), "w_up_s": rand(d, f), "w_down_s": rand(f, d)}
    h = rand(8, 1, d)
    idx = jnp.asarray(picks, jnp.int32)[:, None]
    gate = jax.random.uniform(next(ks), idx.shape, minval=0.1, maxval=1.0)
    monkeypatch.setattr(experts, "route", lambda *_: (idx, gate))
    live = jnp.asarray(live, jnp.float32)
    assert mla_moe.expert_path(cfg, 8, stacks) == "hit_list"
    dense, _ = mla_moe.moe_ffn(h, p | {k: v[place] for k, v in stacks.items()}, cfg, live)
    got, stats = jax.jit(lambda: mla_moe.moe_ffn(
        h, p, cfg, live, "hit_list", tuple(stacks[k] for k in experts.EXPERT_LEAVES), place))()
    assert stats.tolist() == [n_hit, rows_max, int(sum(live))]
    from nats_llm_studio_tpu.ops.layers import swiglu

    shared = swiglu(h, p["w_gate_s"], p["w_up_s"], p["w_down_s"])
    on = np.asarray(live) > 0
    f32 = lambda x: np.asarray(x.astype(jnp.float32))  # noqa: E731
    tol = 2e-4 if dtype == "float32" else 2.0 ** -6 * float(np.abs(f32(dense)).max())
    np.testing.assert_allclose(f32(got)[on], f32(dense)[on], rtol=tol, atol=tol)
    if case in ("no_live_row", "a_dead_slots_picks_stay_out"):
        # the dead rows picked nothing the list holds
        np.testing.assert_allclose(f32(got)[~on], f32(shared)[~on], rtol=tol, atol=tol)
        assert not np.allclose(f32(dense)[~on], f32(shared)[~on], atol=10 * tol)


def _picks(how: str, rows: int, e: int, k: int) -> np.ndarray:
    rng = np.random.default_rng(rows)
    if how == "one_expert_takes_every_row":   # and three others a third of them each
        return np.stack([np.full(rows, 5), 10 + np.arange(rows) % 3,
                         20 + np.arange(rows) % 3, 30 + np.arange(rows) % 3], axis=1)
    if how == "experts_with_no_row":          # the upper half of the experts is never picked
        return np.stack([rng.choice(e // 2, k, replace=False) for _ in range(rows)])
    return np.stack([rng.choice(e, k, replace=False) for _ in range(rows)])   # balanced


@pytest.mark.parametrize("rows", [250, 256, 1000])
@pytest.mark.parametrize("how", ["one_expert_takes_every_row", "balanced", "experts_with_no_row"])
def test_the_grouped_form_is_the_dense_dispatch_without_its_zero_terms(how, rows, monkeypatch):
    """The same layer twice on the same rows, picks and gates: dense dispatch
    (every expert computes every row) and the grouped form (the pairs sorted
    by expert, each on its own expert, over the WHOLE stacks and the layer's
    place in them), to float32-accumulation tolerance; row counts that are
    and are not a multiple of the row tile."""
    cfg = REF.model_config(CONF, SEQ).with_(n_experts=64, n_experts_used=4, dtype="float32")
    d, f, e, k, place = cfg.d_model, cfg.moe_d_ff, cfg.n_experts, cfg.n_experts_used, 1
    ks = iter(jax.random.split(jax.random.PRNGKey(rows), 8))
    rand = lambda *shape: jax.random.normal(next(ks), shape) * 0.3  # noqa: E731
    stacks = {"w_gate_e": rand(2, e, d, f), "w_up_e": rand(2, e, d, f),
              "w_down_e": rand(2, e, f, d)}
    p = {"w_gate_s": rand(d, f), "w_up_s": rand(d, f), "w_down_s": rand(f, d)}
    b = 2 if rows % 2 == 0 else 1
    h = rand(b, rows // b, d)
    idx = jnp.asarray(_picks(how, rows, e, k), jnp.int32).reshape(b, rows // b, k)
    gate = jax.random.uniform(next(ks), idx.shape, minval=0.1, maxval=1.0)
    monkeypatch.setattr(experts, "route", lambda *_: (idx, gate))
    assert mla_moe.expert_path(cfg, rows, stacks) == "grouped"
    dense, _ = jax.jit(lambda: mla_moe.moe_ffn(
        h, p | {k_: v[place] for k_, v in stacks.items()}, cfg))()
    got, stats = jax.jit(lambda: mla_moe.moe_ffn(
        h, p, cfg, None, "grouped", tuple(stacks[k_] for k_ in experts.EXPERT_LEAVES), place))()
    assert stats is None and got.shape == dense.shape
    scale = float(np.abs(np.asarray(dense)).max())
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense), rtol=0, atol=2e-5 * scale)
    # and they are not both the shared expert alone
    from nats_llm_studio_tpu.ops.layers import swiglu

    shared = swiglu(h, p["w_gate_s"], p["w_up_s"], p["w_down_s"])
    assert float(np.abs(np.asarray(got) - np.asarray(shared)).max()) > 0.05 * scale


@pytest.mark.parametrize("rows,leaves,devices,path", [
    (8, "plain", 1, "hit_list"),      # a decode step of the cell: 8 x 4 < 64
    (16, "plain", 1, "grouped"),      # 16 x 4 = 64: the picks reach the experts' count
    (56, "plain", 1, "grouped"),      # a verify bundle: 8 slots x (6 drafts + 1)
    (256, "plain", 1, "grouped"),     # a prefill chunk
    (8, "int8", 1, "dense"),          # WQUANT=int8 expert stacks
    (256, "int8", 1, "dense"),
    (8, "plain", 2, "dense"),         # a mesh of more than one chip
    (256, "plain", 2, "dense"),
])
def test_the_expert_path_is_chosen_from_shapes_leaf_types_and_devices(rows, leaves, devices, path):
    from nats_llm_studio_tpu.ops.wquant import quantize_weight
    from nats_llm_studio_tpu.parallel.mesh import build_mesh

    cfg = REF.model_config(CONF, SEQ).with_(n_experts=64, n_experts_used=4)
    w = jnp.ones((1, 64, 8, 8), jnp.bfloat16)
    stack = {k: w if leaves == "plain" else quantize_weight(w) for k in experts.EXPERT_LEAVES}
    mesh = build_mesh({"tp": devices}, devices=jax.local_devices()[:devices])
    assert mla_moe.expert_path(cfg, rows, stack, mesh) == path
    if devices > 1:   # the mesh alone made it dense
        assert mla_moe.expert_path(cfg, rows, stack) == ("hit_list" if rows == 8 else "grouped")


@pytest.mark.parametrize("rows,iters", [(1, 20), (8, 20), (56, 20), (128, 3), (8, 0),
                                        (256, 20), (1000, 3)])   # a prefill chunk, no lane multiple
def test_the_sinkhorn_kernel_is_the_loop_it_stands_for(rows, iters):
    """A mixer runs its rounds in one kernel (decode: 8 rows, a verify bundle:
    56, a prefill chunk: 256 and more); only more rows than the kernel's one
    block holds keep the ``fori_loop``. Both are the same rounds, rows first."""
    from nats_llm_studio_tpu.ops.sinkhorn import sinkhorn_rounds

    res = jnp.exp(jnp.clip(3 * jax.random.normal(jax.random.PRNGKey(rows), (4, 4, rows)), -30, 30))
    want = res
    for _ in range(iters):
        want = want / (jnp.sum(want, axis=1, keepdims=True) + 1e-6)
        want = want / (jnp.sum(want, axis=0, keepdims=True) + 1e-6)
    got = sinkhorn_rounds(res, iters, 1e-6, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-7)
    if iters == 20:   # doubly stochastic by then
        np.testing.assert_allclose(np.asarray(got.sum(0)), 1.0, atol=1e-3)


def test_absorbed_attention_is_expanded_attention():
    cfg = REF.model_config(CONF, SEQ).with_(dtype="float32")
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    b, t, s, hq = 2, 5, 48, cfg.n_heads
    q_nope = jax.random.normal(ks[0], (b, t, hq, cfg.qk_nope_head_dim))
    q_rope = jax.random.normal(ks[1], (b, t, hq, cfg.qk_rope_head_dim))
    c_win = jax.random.normal(ks[2], (b, s, cfg.kv_lora_rank))
    kr_win = jax.random.normal(ks[3], (b, s, cfg.qk_rope_head_dim))
    p = {"w_ukv": jax.random.normal(ks[4], (cfg.kv_lora_rank, hq * (
        cfg.qk_nope_head_dim + cfg.v_head_dim))) * 0.1}
    positions = jnp.asarray([[20, 21, 22, 23, 24], [40, 41, 42, 43, 44]], jnp.int32)
    from nats_llm_studio_tpu.ops.mla_attention import mla_absorbed_attention

    with jax.default_matmul_precision("highest"):
        want = expanded_attention(q_nope, q_rope, c_win, kr_win, p, cfg, positions)
        got = mla_moe.absorbed_output(mla_absorbed_attention(
            mla_moe.absorbed_queries(q_nope, p, cfg), q_rope, c_win, kr_win, positions,
            cfg.attn_scale), p, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


_KERNEL_SLOTS, _KERNEL_RUNS = 6, 3.2  # the table holds three runs and a fifth


@pytest.fixture(scope="module")
def kernel_case():
    """``_kernel_case`` made once a (T, W) and dropped with the module: the
    pools are 50 MB a T."""
    made = {}
    yield lambda t, w: made.get((t, w)) or made.setdefault((t, w), _kernel_case(t, w))
    made.clear()


def _kernel_case(t, w):
    """Shapes at which ``_run_entries`` gives a run well inside the table (32
    heads over one latent head of 384 + 128 in float32: 2,048 B a token, 640
    tokens a run), the pools, and the two jitted paths: one compile a (T, W)
    for every pattern of contexts."""
    from nats_llm_studio_tpu.ops.mla_attention import (
        _run_entries, mla_absorbed_attention, mla_paged_decode_attention)
    from nats_llm_studio_tpu.ops.ssm_scan import live_slots

    b, hq, r, dr, layers = _KERNEL_SLOTS, 32, 384, 128, 2
    k = _run_entries(t, 1 << 20, (r + dr) * 4)
    nb = int(k * _KERNEL_RUNS)
    assert _run_entries(t, nb, (r + dr) * 4) == k and 1 < k < nb
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    qt = jax.random.normal(ks[0], (b, w, hq, r), jnp.float32)
    qr = jax.random.normal(ks[1], (b, w, hq, dr), jnp.float32)
    c_pool = jax.random.normal(ks[2], (1 + b * nb, layers, 1, t, r), jnp.float32)
    r_pool = jax.random.normal(ks[3], (1 + b * nb, layers, 1, t, dr), jnp.float32)

    @jax.jit
    def kernel(c_pool, r_pool, tbl, pos):
        return mla_paged_decode_attention(qt, qr, c_pool, r_pool, tbl, pos,
                                          live_slots(tbl[:, 0] > 0), 1, 0.11, interpret=True)

    @jax.jit
    def xla(c_pool, r_pool, tbl, pos):
        view = lambda pool: pool[tbl, 1, 0].reshape(b, nb * t, -1)  # noqa: E731
        return mla_absorbed_attention(qt, qr, view(c_pool), view(r_pool),
                                      pos[:, None] + jnp.arange(w)[None], 0.11)

    return k, nb, c_pool, r_pool, kernel, xla


# the last key a slot sees, in runs (n), blocks (t) and tokens; None: no request
_KERNEL_PATTERNS = {
    "empty_first_then_a_runs_edge": lambda n, t, end: [None, 5, n - 2, n - 1, n, n - t // 2],
    "empty_between_and_last": lambda n, t, end: [2 * n + n // 3, n + 1, None, t - 1, 3, None],
    "all_but_one_empty": lambda n, t, end: [None, None, None, 2 * n + 5, None, None],
    "none_live": lambda n, t, end: [None] * 6,
    "all_live_to_the_tables_end": lambda n, t, end: [end - 1, n, 2 * n - 1, 2, 3 * n, t],
}


@pytest.mark.parametrize("pattern", list(_KERNEL_PATTERNS))
@pytest.mark.parametrize("w", [1, 3])
@pytest.mark.parametrize("t", [16, 64])
def test_the_pallas_kernel_is_the_xla_path_over_its_live_slots(kernel_case, t, w, pattern):
    """The kernel through the Pallas interpreter against the XLA form over
    the gathered view: tables in no order of the pool, contexts that end
    inside the first block, one token before / at / after a run's edge, inside
    a run's last block and several runs on, slots without a request before,
    between and after the others. Every block the walk has no business with
    (the null block, a live slot's entries past its last live block, all of an
    empty slot's) holds NaN in the pools the kernel gets: a live row comes out
    as the XLA path's over the same pools without them, an empty row as exact
    zeros."""
    k, nb, c_pool, r_pool, kernel, xla = kernel_case(t, w)
    last_keys = _KERNEL_PATTERNS[pattern](k * t, t, nb * t)
    assert len(last_keys) == _KERNEL_SLOTS
    rng = np.random.default_rng(len(pattern))
    ids = rng.permutation(np.arange(1, 1 + _KERNEL_SLOTS * nb)).reshape(_KERNEL_SLOTS, nb)
    live = np.array([x is not None for x in last_keys])
    tbl = np.where(live[:, None], ids, 0).astype(np.int32)
    pos = np.array([77 if x is None else max(x - (w - 1), 0) for x in last_keys], np.int32)
    read = np.zeros(c_pool.shape[0], bool)
    for slot in np.flatnonzero(live):
        read[tbl[slot, : (pos[slot] + w - 1) // t + 1]] = True
    unread = jnp.asarray(~read)[:, None, None, None, None]
    got = np.asarray(kernel(jnp.where(unread, jnp.nan, c_pool), jnp.where(unread, jnp.nan, r_pool),
                            jnp.asarray(tbl), jnp.asarray(pos)))
    want = np.asarray(xla(c_pool, r_pool, jnp.asarray(tbl), jnp.asarray(pos)))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[live], want[live], rtol=2e-4, atol=2e-4)
    assert not got[~live].any()


def test_yarn_is_its_closed_form_below_and_above_the_original_context():
    hf = json.loads((ROOT / "benchmark/configs/xing4.0-29b-a4b.json").read_text())
    cfg = REF.model_config(hf, 4096)
    inv = mla_moe.yarn_inv_freq(cfg)
    i = np.arange(32)
    plain = 10000.0 ** (-2 * i / 64)
    # the correction range of beta_fast 32 / beta_slow 1 over 4,096 positions:
    # dims below 11 turn too fast to need scaling, dims from 23 on take all of it
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 64, rtol=1e-6)
    ramp = (i[11:23] - 10) / (23 - 10)
    np.testing.assert_allclose(inv[11:23], plain[11:23] / 64 * ramp + plain[11:23] * (1 - ramp),
                               rtol=1e-6)
    np.testing.assert_allclose(inv, REF.yarn_inv_freq(hf), rtol=1e-6)
    for pos in (17, 4095, 4097, 200_000):
        cos, sin = mla_moe.rope_tables(cfg, jnp.asarray([pos], jnp.int32))
        np.testing.assert_allclose(np.asarray(cos[0]), np.cos(pos * inv.astype(np.float64)), atol=2e-2)
        np.testing.assert_allclose(np.asarray(sin[0]), np.sin(pos * inv.astype(np.float64)), atol=2e-2)
    assert abs(cfg.attn_scale - 192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2) < 1e-9


def test_the_metadata_round_trip_keeps_the_family():
    from nats_llm_studio_tpu.models.export import config_metadata

    hf = json.loads((ROOT / "benchmark/configs/xing4.0-29b-a4b.json").read_text())
    cfg = REF.model_config(hf, 4096)
    back = ModelConfig.from_gguf_metadata(config_metadata(cfg, "m")).with_(dtype=cfg.dtype)
    for name in cfg.__dataclass_fields__:
        a, b = getattr(cfg, name), getattr(back, name)
        assert a == pytest.approx(b, rel=1e-6) if isinstance(a, float) else a == b, name
    assert back.is_mla and back.n_moe_layers == 6
    assert back.kv_cache_dims() == ((1, 512), (1, 128))   # the rotary key's rows lane-padded


@pytest.mark.parametrize("how,cause", [
    (dict(paged=False), "paged pool only"),
    (dict(cfg=dict(kv_quant="int8")), "TPU_KV_QUANT=int8 is not implemented for a latent cache"),
    (dict(kv_tiers=object()), "set KV_HOST_POOL_BYTES=0"),
])
def test_what_the_family_does_not_serve_is_refused_with_its_cause(model, how, cause):
    from nats_llm_studio_tpu.serve.batcher import ContinuousBatcher

    cfg, params = model
    with pytest.raises(ValueError, match=cause):
        ContinuousBatcher(params, cfg.with_(**how.get("cfg", {})), max_slots=2,
                          **{k: v for k, v in how.items() if k != "cfg"})


def test_a_mesh_of_more_than_one_chip_is_refused_with_its_cause(model):
    from nats_llm_studio_tpu.parallel.mesh import build_mesh
    from nats_llm_studio_tpu.parallel.sharding import validate_mesh_for_config

    cfg, _ = model
    with pytest.raises(ValueError, match="serve on one chip a replica"):
        validate_mesh_for_config(build_mesh({"tp": 2}, devices=jax.local_devices()[:2]), cfg)


def test_a_bursts_expert_counters_are_summed_and_named():
    from nats_llm_studio_tpu.serve.batcher import BatcherStats

    st = BatcherStats()
    # two expert layers x (hit, rows max, live rows), three steps
    burst = st.record_moe(np.asarray([[5, 6, 7], [2, 2, 3], [4, 4, 4],
                                      [8, 8, 8], [1, 1, 1], [4, 4, 4]]))
    assert burst == {"experts_hit": 42, "expert_rows_max": 10, "expert_rows": 24,
                     "expert_steps": 6}
    assert st.moe_counters() == burst and st.record_moe(np.zeros((3, 2), int))["expert_steps"] == 2
    assert st.expert_steps == 8
    # the batcher names the form its decode bursts take; the readback span
    # carries it beside the sums, the counters stay numbers
    st.expert_path = "hit_list"
    assert st.record_moe(np.zeros((3, 1), int)) == {
        "experts_hit": 0, "expert_rows_max": 0, "expert_rows": 0, "expert_steps": 1,
        "expert_path": "hit_list"}
    assert "expert_path" not in st.moe_counters()
