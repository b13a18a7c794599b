"""The window / full attention family with a gated attention output and
routed experts (``models/swa_moe.py``, ``ops/paged_attention.py
window_decode_attention``) against its plain reference
(``benchmark/references/swa_gated_moe.py``) on seeded weights, at toy size on
the CPU: logits, not tokens. The served side is driven the way the batcher
drives it: ``models.llama.forward`` prefill (whole, in chunks across the
window's edge, or as a padded group) into row caches that carry the rows'
rings, written into the pool (the full layers' KV by table, the rings by
slot), then ``forward_decode_paged`` steps (both Pallas kernels in interpreter
mode) for more steps than a ring is long. The toy keeps both layer kinds, 4
query heads in a full layer and 6 in a window layer, a window of 16 SHORTER
than every context, half a head rotated in the full layers and YaRN past its
original 32 positions. Faults put in on purpose must each fail the toy limits
(``tests/test_swa_moe_faults.py``); the live batcher drives the same toy in
``tests/test_swa_moe_served.py``: three files, so that three workers share
what is the longest set of tier 1 on an empty compile cache.

The model-configs guide's share-sum test (the shares of a layer's experts add
up to the whole layer) does not apply: the configuration holds all of a
layer's experts on the chip, so there is no share to add up."""

import json
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run
from benchmark.lib import correct, weights
from nats_llm_studio_tpu.models import llama, swa_moe
from nats_llm_studio_tpu.models.config import ModelConfig
from nats_llm_studio_tpu.ops import paged_attention
from nats_llm_studio_tpu.ops.kvcache import (
    WithState, kv_pool_write_row, kv_pool_zeros, state_row, state_write_row)

ROOT = Path(__file__).resolve().parents[1]
CONF = json.loads((ROOT / "benchmark/tests/rehearsal/configs/tiny-swa.json").read_text())
REF = run.load_module(ROOT / "benchmark/references/swa_gated_moe.py")

T, SEQ, SLOTS = 16, 128, 3  # pool block tokens; a slot's table spans SEQ
WINDOW = 16
PROMPT = 40                 # 2.5 windows, not a multiple of T
STEPS = 24                  # more than a ring is long: every place is rewritten
SLOT = 1
TABLE = [3, 5, 2, 7, 1, 4, 6, 8]
# float32 through five toy layers: the sound path agrees to ~1e-5 (the only
# difference is the order of float32 sums: blocks against dense, the ring's
# order against position order), so the limits sit three orders above it and
# every fault far above them
TOY_FIRST = {"median_tol": 0.02, "token_tol": 0.05}
TOY_DECODED = {"median_tol": 0.02, "token_tol": 0.05, "gap_tol": 0.05}


@pytest.fixture(scope="module")
def model():
    mp = pytest.MonkeyPatch()
    # N(0, 0.02) adds little at d 128: at 0.08 and the reference's wq / wk x1.5
    # the scores' std is ~1.8, as at the published widths
    mp.setattr(weights, "INIT_STD", 0.08)
    try:
        from nats_llm_studio_tpu.parallel.mesh import build_mesh

        cfg = REF.model_config(CONF, SEQ).with_(dtype="float32")
        mesh = build_mesh({"tp": 1}, devices=jax.local_devices()[:1])
        # the schema is the reference's param_shapes, the gains its
        # weight_gains, the placement the program's rule for every leaf. The
        # router alone is drawn at x1 here: the cell silences it (x0) so that
        # every seed hits the same number of experts a step, and a test has
        # to see the router's product, its sigmoid and the gates it gives
        family = types.SimpleNamespace(
            param_shapes=REF.param_shapes, weight_gains=dict(REF.weight_gains, router=1.0))
        yield cfg, weights.make_seeded_params(4321, family)(None, cfg, mesh)
    finally:
        mp.undo()


def tokens(seed: int, n: int) -> list[int]:
    return [int(t) for t in np.random.default_rng(seed).integers(32, 127, size=n)]


@pytest.fixture(scope="module")
def prompt():
    return tokens(1, PROMPT)


def entry(logits) -> dict:
    lp = np.asarray(jax.nn.log_softmax(jnp.asarray(logits, jnp.float32)))

    def one(i):
        return {"token": chr(int(i)), "bytes": [int(i)], "logprob": float(lp[i])}

    return dict(one(int(np.argmax(lp))),
                top_logprobs=[one(i) for i in np.argsort(-lp)[:correct.TOP_K]])


def empty_pools(cfg):
    shape = (1 + 2 * len(TABLE), cfg.n_kv_layers, cfg.n_kv_heads, T, cfg.head_dim)
    return tuple(WithState(kv_pool_zeros(shape, jnp.dtype(cfg.dtype)), st, ax)
                 for st, ax in swa_moe.make_state(cfg, SLOTS))


def prefill(cfg, params, prompt, chunks=None, pad=0, mask_padding=True):
    """``prompt`` into a fresh row cache, in ``chunks``, the last chunk
    right-padded by ``pad`` positions as an admit bucket pads it. Returns
    (the logits after the prompt's last position, the row caches)."""
    k, v = llama.make_cache(cfg, 1, SEQ)
    at = 0
    for c in chunks or (len(prompt),):
        last = at + c == len(prompt)
        toks = prompt[at: at + c] + [0] * (pad if last else 0)
        ends = jnp.asarray([c - 1], jnp.int32)
        logits, k, v = llama.forward(
            params, cfg, jnp.asarray([toks], jnp.int32), k, v, jnp.asarray([at], jnp.int32),
            logit_positions=ends if mask_padding else None, fresh_prefill=at == 0,
            uniform_start=True)
        if not mask_padding:
            logits = logits[:, c - 1: c]
        at += c
    return logits[0, -1], (k, v)


def into_pool(pools, rows, slot=SLOT, with_ring=True):
    """A prefilled row's KV into the table's blocks and its rings into the
    slot's row: what ``serve/programs.py pool_write`` does."""
    bids = jnp.asarray(TABLE, jnp.int32)
    return tuple(
        WithState(kv_pool_write_row(p.kv, r.kv, bids),
                  state_write_row(p, r.st, slot) if with_ring else p.st, p.axes)
        for p, r in zip(pools, rows))


def decode(cfg, params, pools, first, pos, n, slot=SLOT):
    """n greedy steps of ``slot`` through the paged decode path; the other
    slots ride along on token 0, as empty slots do."""
    tbl = np.zeros((SLOTS, len(TABLE)), np.int32)
    tbl[slot] = TABLE
    tbl = jnp.asarray(tbl)
    step = jax.jit(lambda tok, kp, vp, pos: llama.forward_decode_paged(
        params, cfg, tok, kp, vp, tbl, pos, moe_stats=True))
    entries = [first]
    kp, vp = pools
    for i in range(n):
        tok = np.zeros((SLOTS, 1), np.int32)
        tok[slot] = entries[-1]["bytes"][0]
        at = np.zeros((SLOTS,), np.int32)
        at[slot] = pos + i
        logits, kp, vp, st = step(jnp.asarray(tok), kp, vp, jnp.asarray(at))
        # the counters of every expert layer: one live row, its top-2
        assert np.asarray(st).tolist() == [[2, 1, 1]] * cfg.n_moe_layers
        entries.append(entry(logits[slot, -1]))
    return entries, (kp, vp)


def serve(cfg, params, prompt, n, **how):
    logits, rows = prefill(cfg, params, prompt, **how)
    pools = into_pool(empty_pools(cfg), rows)
    return decode(cfg, params, pools, entry(logits), len(prompt), n - 1)[0]


def check(params, prompt, entries) -> dict:
    toks = correct.served_tokens(entries)
    ref = REF.tail_logprobs(params, CONF, list(prompt) + toks[:-1], len(toks))
    return correct.compare_probes([(ref, entries)], TOY_FIRST, TOY_DECODED)


def test_the_toy_keeps_what_the_family_is_made_of(model):
    cfg, params = model
    assert cfg.family == "swa_moe" and cfg.layer_types == ("full",) + ("window",) * 3 + ("full",)
    assert (cfg.n_kv_layers, cfg.n_win_layers, cfg.n_dense_layers, cfg.n_moe_layers) == (2, 3, 1, 4)
    assert (cfg.n_heads, cfg.win_n_heads, cfg.n_kv_heads) == (4, 6, 2)
    assert cfg.window == WINDOW < PROMPT and cfg.rope_dim == 16 and cfg.win_rope_dim == 32
    assert cfg.rope_orig_ctx == 32 < PROMPT and cfg.rope_factor == 8 and cfg.attn_gate
    plan = swa_moe.layer_plan(cfg)
    assert plan["leading"] == [("full", 0, 0)] and plan["periods"] == 1 and not plan["tail"]
    assert plan["runs"] == [("window", 0, 3, 0), ("full", 0, 1, 3)]
    blocks = params["blocks"]
    assert blocks["full"]["wq"].shape == (2, 128, 4 * 32) and blocks["full"]["wg"].shape == (2, 128, 4)
    assert blocks["win"]["wq"].shape == (3, 128, 6 * 32) and blocks["win"]["wg"].shape == (3, 128, 6)
    assert blocks["moe"]["w_gate_e"].shape == (4, 16, 128, 32) and "w_gate_e" not in blocks["dense"]


def test_a_whole_model_of_forty_layers_has_a_tail_of_three_window_layers():
    """The published pattern: (full, window x3) x 10 with one leading dense
    layer is the dense layer, nine periods of (window x3, full) and a last,
    partial period of three window layers."""
    cfg = ModelConfig.tiny(n_layers=40, n_dense_layers=1, n_experts=4, n_experts_used=2,
                           layer_types=("full", "window", "window", "window") * 10,
                           window=8, win_n_heads=4)
    plan = swa_moe.layer_plan(cfg)
    assert plan["periods"] == 9 and plan["period"] == 4
    assert plan["tail"] == [("window", 27, 36), ("window", 28, 37), ("window", 29, 38)]
    assert plan["base"] == {"full": 1, "window": 0} and plan["per"] == {"full": 1, "window": 3}


def test_prefill_then_24_paged_decode_steps_agree_with_the_reference(model, prompt):
    """A prompt of 2.5 windows, then 24 steps through pool + ring: the ring of
    16 places is rewritten one and a half times, the table opens block 3."""
    cfg, params = model
    out = check(params, prompt, serve(cfg, params, prompt, STEPS + 1))
    assert out["ok"] and out["first_ok"] and out["decoded"]["ok"], out
    assert out["decoded"]["positions"] == STEPS
    assert out["max_abs_diff"] < 1e-3 and out["decoded"]["max_abs_diff"] < 1e-3, out


@pytest.mark.parametrize("chunks,pad", [((17, 17, 6), 0), ((40,), 24), ((32, 8), 8), ((8, 8, 24), 0)],
                         ids=["three chunks", "one padded bucket", "two chunks, the last padded",
                              "chunks shorter than the window"])
def test_a_prompt_prefilled_in_chunks_across_the_windows_edge_is_one_prefill(
        model, prompt, chunks, pad):
    """A chunk after the first reads the ring the chunk before left (its
    queries see keys of the chunk before, across the window's edge) and the
    full layers' keys back from the row cache; padding behind the prompt gets
    into no ring."""
    cfg, params = model
    whole, (k0, v0) = prefill(cfg, params, prompt)
    parts, (k1, v1) = prefill(cfg, params, prompt, chunks=chunks, pad=pad)
    np.testing.assert_allclose(parts, whole, atol=2e-4)
    for a, b in zip(k0.st + v0.st, k1.st + v1.st):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4)
    out = check(params, prompt, serve(cfg, params, prompt, 4, chunks=chunks, pad=pad))
    assert out["ok"] and out["first_ok"] and out["decoded"]["ok"], out


def test_a_prompt_shorter_than_the_window_is_the_plain_causal_case(model):
    cfg, params = model
    short = tokens(5, 9)
    out = check(params, short, serve(cfg, params, short, 12))
    assert out["ok"] and out["decoded"]["max_abs_diff"] < 1e-3, out


def test_a_group_admit_of_prompts_of_unequal_length_is_each_alone(model):
    """Rows right-padded to one bucket, ``logit_positions`` their ends: a
    row's ring holds its own last real positions, and a row with no real
    position in a chunk (its prompt ended in an earlier one: -1) keeps its
    ring."""
    cfg, params = model
    lens = [24, 9, 17]
    prompts = [tokens(10 + i, n) for i, n in enumerate(lens)]
    k, v = llama.make_cache(cfg, 3, SEQ)
    padded = jnp.asarray([p + [0] * (24 - len(p)) for p in prompts], jnp.int32)
    logits, k, v = llama.forward(
        params, cfg, padded, k, v, jnp.zeros((3,), jnp.int32),
        logit_positions=jnp.asarray([n - 1 for n in lens], jnp.int32), fresh_prefill=True)
    more = tokens(20, 8)   # a second chunk in which only row 0 goes on
    logits2, k2, v2 = llama.forward(
        params, cfg, jnp.asarray([more, [0] * 8, [0] * 8], jnp.int32), k, v,
        jnp.full((3,), 24, jnp.int32), logit_positions=jnp.asarray([7, -1, -1], jnp.int32),
        uniform_start=True)
    for i, p in enumerate(prompts):
        alone, (ka, va) = prefill(cfg, params, p)
        np.testing.assert_allclose(logits[i, 0], alone, atol=2e-4)
        for row, one, kept in zip(state_row(k, i) + state_row(v, i), ka.st + va.st,
                                  state_row(k2, i) + state_row(v2, i)):
            # the places a prompt shorter than the window never wrote are
            # junk on both sides (masked by position): compare what is real
            real = min(lens[i], WINDOW)
            places = [q % WINDOW for q in range(lens[i] - real, lens[i])]
            np.testing.assert_allclose(row[:, :, :, places], one[:, :, :, places],
                                       atol=2e-5, rtol=1e-4)
            if i:
                np.testing.assert_array_equal(kept, row)
    longer, _ = prefill(cfg, params, prompts[0] + more)
    np.testing.assert_allclose(logits2[0, 0], longer, atol=2e-4)


def test_a_replayed_position_writes_its_ring_place_again_and_changes_nothing(model, prompt):
    """The batcher steps a request with logprobs back onto its last prompt
    position: the same key lands on the same place."""
    cfg, params = model
    logits, rows = prefill(cfg, params, prompt)
    pools = into_pool(empty_pools(cfg), rows)
    first = dict(entry(logits), bytes=[prompt[-1]])  # the carry holds prompt[-1] again
    entries, (kp, vp) = decode(cfg, params, pools, first, len(prompt) - 1, 1)
    # the decode path's sums in another order than the prefill's: 2e-5 here
    np.testing.assert_allclose(entries[1]["logprob"], entry(logits)["logprob"], atol=2e-4)
    for before, after in zip(pools, (kp, vp)):
        for a, b in zip(state_row(before, SLOT), state_row(after, SLOT)):
            np.testing.assert_allclose(a, b, atol=2e-5)


def ring_attention_xla(q, k_ring, v_ring, pos, layer, scale):
    """``window_decode_attention`` as plain array operations."""
    b, _, hq, d = q.shape
    hkv, r = k_ring.shape[2], k_ring.shape[3]
    qg = q.reshape(b, hkv, hq // hkv, d)
    s = jnp.einsum("bhgd,bhrd->bhgr", qg, k_ring[layer]) * scale
    age = jnp.mod(pos[:, None] - jnp.arange(r, dtype=jnp.int32)[None, :], r)
    s = jnp.where((age <= pos[:, None])[:, None, None, :], s, -1e30)
    o = jnp.einsum("bhgr,bhrd->bhgd", jax.nn.softmax(s, axis=-1), v_ring[layer])
    return o.reshape(b, 1, hq, d)


@pytest.mark.parametrize("group,pos", [(3, [5, 15, 16, 100]), (8, [0, 31, 47, 1000])])
def test_the_ring_kernel_is_the_xla_form(group, pos):
    """Interpreter mode against plain XLA at contexts shorter than the ring,
    exactly it, one past it and many wraps on; the other layer's ring holds
    NaN and is never read."""
    b, hkv, r, d, layers = len(pos), 2, 16, 32, 2
    ks = jax.random.split(jax.random.PRNGKey(group), 3)
    q = jax.random.normal(ks[0], (b, 1, hkv * group, d))
    rk, rv = (jax.random.normal(k_, (layers, b, hkv, r, d)).at[0].set(jnp.nan) for k_ in ks[1:])
    at = jnp.asarray(pos, jnp.int32)
    got = paged_attention.window_decode_attention_auto(q, rk, rv, at, 1, d ** -0.5)
    want = ring_attention_xla(q, rk, rv, at, 1, d ** -0.5)
    # one softmax over the whole ring on both sides: float32 rounding of the
    # two products' order is all that differs
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=1e-5)
    # a context shorter than the ring ignores the places it has not written
    junk = rk.at[1, 0, :, pos[0] + 1:].set(1e4)
    again = paged_attention.window_decode_attention_auto(q, junk, rv, at, 1, d ** -0.5)
    np.testing.assert_array_equal(again[0], got[0])


# -- metadata, pricing, refusals -------------------------------------------------


def test_the_metadata_round_trip_keeps_the_family(model):
    from nats_llm_studio_tpu.models.export import config_metadata

    cfg, _ = model
    back = ModelConfig.from_gguf_metadata(config_metadata(cfg, "m")).with_(dtype=cfg.dtype)
    assert back == cfg
    assert back.family == "swa_moe" and back.slot_state and back.n_moe_layers == 4


def test_the_published_configuration_maps_and_is_priced_by_kind_of_cache():
    from nats_llm_studio_tpu.parallel.memory import (
        estimate_device_bytes, kv_pool_block_bytes, state_slot_bytes)

    conf = json.loads((ROOT / "benchmark/configs/laguna-xs.2.json").read_text())
    cfg = REF.model_config(conf, 18432)
    assert cfg.layer_types == ("full", "window", "window", "window", "full")
    assert (cfg.n_heads, cfg.win_n_heads, cfg.n_kv_heads, cfg.head_dim) == (48, 64, 8, 128)
    assert (cfg.window, cfg.rope_dim, cfg.win_rope_dim) == (512, 64, 128)
    assert (cfg.rope_theta, cfg.win_rope_theta, cfg.rope_factor) == (5e5, 1e4, 64.0)
    assert abs(cfg.rope_attn_factor - 1.4158883) < 1e-6 and cfg.attn_scale == 128 ** -0.5
    assert (cfg.n_experts, cfg.n_experts_used, cfg.moe_d_ff, cfg.routed_scaling) == (256, 8, 512, 2.5)
    # the pool's blocks hold the 2 full layers only: 16 tokens x 8 heads x 128 x K, V x bf16
    assert kv_pool_block_bytes(cfg, 16) == 2 * 2 * 16 * 8 * 128 * 2
    # a slot's rings: 3 window layers x 512 keys x 8 heads x 128 x K, V x bf16
    assert state_slot_bytes(cfg) == 3 * 512 * 8 * 128 * 2 * 2
    est = estimate_device_bytes(cfg, {}, batch=16, seq_len=18432)
    # ISSUE.md's table: 3,869,856,768 parameters less the norms and the bias
    assert abs(est["params"] - 2 * 3_869_856_768) < 2 * 40_000
    assert est["kv_cache"] == 16 * 18432 * 2 * 2 * 8 * 128 * 2 + 16 * state_slot_bytes(cfg)


@pytest.mark.parametrize("how,cause", [
    (dict(paged=False), "paged pool only"),
    (dict(cfg=dict(kv_quant="int8")), "TPU_KV_QUANT=int8 is not implemented for window-attention"),
    (dict(kv_tiers=object()), "set KV_HOST_POOL_BYTES=0"),
    (dict(env=dict(DECODE_KERNEL="xla")), "decode on the pool and the ring in place only"),
])
def test_what_the_family_does_not_serve_is_refused_with_its_cause(model, how, cause,
                                                                  monkeypatch):
    from nats_llm_studio_tpu.serve.batcher import ContinuousBatcher

    cfg, params = model
    for k, v in how.get("env", {}).items():
        monkeypatch.setenv(k, v)
    with pytest.raises(ValueError, match=cause):
        ContinuousBatcher(params, cfg.with_(**how.get("cfg", {})), max_slots=2,
                          **{k: v for k, v in how.items() if k not in ("cfg", "env")})


def test_a_mesh_kvx1_a_verify_bundle_and_a_gguf_of_tensors_are_refused_with_their_causes(model):
    from nats_llm_studio_tpu.parallel.loader import load_params_sharded
    from nats_llm_studio_tpu.parallel.mesh import build_mesh
    from nats_llm_studio_tpu.parallel.sharding import validate_mesh_for_config
    from nats_llm_studio_tpu.serve.batcher import ContinuousBatcher

    cfg, params = model
    with pytest.raises(ValueError, match="serve on one chip a replica"):
        validate_mesh_for_config(build_mesh({"tp": 2}, devices=jax.local_devices()[:2]), cfg)
    with pytest.raises(NotImplementedError, match="one position a step"):
        kp, vp = empty_pools(cfg)
        llama.forward_decode_paged(params, cfg, jnp.zeros((SLOTS, 3), jnp.int32), kp, vp,
                                   jnp.zeros((SLOTS, 8), jnp.int32), jnp.zeros((SLOTS,), jnp.int32))
    with pytest.raises(NotImplementedError, match="no GGUF tensor-name map for window-attention"):
        load_params_sharded(None, cfg, build_mesh({"tp": 1}, devices=jax.local_devices()[:1]))
    b = ContinuousBatcher(params, cfg, max_slots=2)
    try:
        with pytest.raises(ValueError, match="KVX1 carries KV blocks and no ring"):
            b.export_prefix_blocks([1, 2, 3])
    finally:
        b.stop()


def test_the_window_counters_count_keys_a_layer_of_each_kind():
    from nats_llm_studio_tpu.serve.batcher import BatcherStats

    st = BatcherStats()
    # rows at positions 3 and 600, two steps: full 4 + 5 + 601 + 602, window 4 + 5 + 512 + 512
    assert st.record_window([3, 600], 2, 512) == {
        "win_tokens": 1033, "full_tokens": 1212, "win_steps": 2}
    assert st.record_window([0], 1, 512) == {"win_tokens": 1, "full_tokens": 1, "win_steps": 1}
    assert st.window_counters() == {"win_tokens": 1034, "full_tokens": 1213, "win_steps": 3,
                                    "ring_tokens": 0}
