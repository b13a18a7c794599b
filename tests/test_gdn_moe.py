"""The linear-attention / gated-attention family with routed experts
(``models/gdn_moe.py``, ``ops/gated_delta.py``, the share of the experts in
``models/experts.py``) against its plain reference
(``benchmark/references/gdn_moe.py``: the delta rule a token at a time) on
seeded weights with a LIVE router, at toy size on the CPU: logits, not tokens.
The served side is driven the way the batcher drives it: ``models.llama.
forward`` prefill (whole, in chunks, or as a padded group) into row caches
that carry the rows' state, written into the pool (KV by table, state by
slot), then ``forward_decode_paged`` steps (the state kernel, the paged
attention kernel and the expert kernels in interpreter mode). The toy is a
period and a half (linear x3, attention, linear x2), holds 8 of 32 experts
(rank 1 of 4), rotates a quarter of a head. Faults put in on purpose are in
``tests/test_gdn_moe_faults.py``, the live batcher in
``tests/test_gdn_moe_served.py``: three files, so that three workers share
them."""

import json
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run
from benchmark.lib import correct, weights
from nats_llm_studio_tpu.models import experts, gdn_moe, llama
from nats_llm_studio_tpu.models.config import ModelConfig
from nats_llm_studio_tpu.ops import gated_delta, ssm_scan
from nats_llm_studio_tpu.ops.kvcache import (
    WithState, kv_pool_write_row, kv_pool_zeros, state_row, state_write_row)

ROOT = Path(__file__).resolve().parents[1]
CONF = json.loads((ROOT / "benchmark/tests/rehearsal/configs/tiny-gdn.json").read_text())
REF = run.load_module(ROOT / "benchmark/references/gdn_moe.py")

T, SEQ, SLOTS = 16, 128, 3  # pool block tokens; a slot's table spans SEQ
PROMPT = 40                 # not a multiple of T; under the rule's chunk of 64
STEPS = 24
SLOT = 1
TABLE = [3, 5, 2, 7, 1, 4, 6, 8]
# float32 through six toy layers: the sound path agrees to ~1e-3 on logits of
# size 40. That is the delta rule's own conditioning and not a path's: u =
# beta (v - S^T k) is a difference the rule drives towards zero wherever a key
# comes again, so the float32 sums' order (chunked against sequential) shows
# at 1e-4 relative in u and from there in the state. The limits sit more than
# an order above it and every fault far above them
TOY_FIRST = {"median_tol": 0.02, "token_tol": 0.05}
TOY_DECODED = {"median_tol": 0.02, "token_tol": 0.05, "gap_tol": 0.05}
# the cell may silence the router; a test has to see its product, its softmax
# and the gates it gives
GAINS = dict(REF.weight_gains, router=4.0)


def seeded(cfg, conf_gains=GAINS, seed=4321):
    from nats_llm_studio_tpu.parallel.mesh import build_mesh

    mesh = build_mesh({"tp": 1}, devices=jax.local_devices()[:1])
    family = types.SimpleNamespace(param_shapes=REF.param_shapes, weight_gains=conf_gains)
    return weights.make_seeded_params(seed, family)(None, cfg, mesh)


@pytest.fixture(scope="module")
def model():
    mp = pytest.MonkeyPatch()
    mp.setattr(weights, "INIT_STD", 0.08)   # N(0, 0.02) adds little at d 128
    try:
        cfg = REF.model_config(CONF, SEQ).with_(dtype="float32")
        yield cfg, seeded(cfg)
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
    (h, w), _ = cfg.kv_cache_dims()
    shape = (1 + 2 * len(TABLE), cfg.n_kv_layers, h, T, w)
    return tuple(WithState(kv_pool_zeros(shape, jnp.dtype(cfg.dtype)), st, ax)
                 for st, ax in gdn_moe.make_state(cfg, SLOTS))


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
            logit_positions=ends if mask_padding else None, fresh_prefill=at == 0)
        if not mask_padding:
            logits = logits[:, c - 1: c]
        at += c
    return logits[0, -1], (k, v)


def into_pool(pools, rows, slot=SLOT, with_state=True):
    """A prefilled row's KV into the table's blocks and its state into the
    slot's row: what ``serve/programs.py pool_write`` does."""
    bids = jnp.asarray(TABLE, jnp.int32)
    return tuple(
        WithState(kv_pool_write_row(p.kv, r.kv, bids),
                  state_write_row(p, r.st, slot) if with_state else p.st, p.axes)
        for p, r in zip(pools, rows))


def decode(cfg, params, pools, first, pos, n, slot=SLOT):
    """n greedy steps of ``slot`` through the paged decode path; the other
    slots ride along on token 0, as empty slots do."""
    tbl = np.zeros((SLOTS, len(TABLE)), np.int32)
    tbl[slot] = TABLE
    tbl = jnp.asarray(tbl)
    step = jax.jit(lambda tok, kp, vp, pos: llama.forward_decode_paged(
        params, cfg, tok, kp, vp, tbl, pos))
    entries = [first]
    kp, vp = pools
    for i in range(n):
        tok = np.zeros((SLOTS, 1), np.int32)
        tok[slot] = entries[-1]["bytes"][0]
        at = np.zeros((SLOTS,), np.int32)
        at[slot] = pos + i
        logits, kp, vp = step(jnp.asarray(tok), kp, vp, jnp.asarray(at))
        entries.append(entry(logits[slot, -1]))
    return entries, (kp, vp)


def serve(cfg, params, prompt, n, **how):
    logits, rows = prefill(cfg, params, prompt, **how)
    pools = into_pool(empty_pools(cfg), rows)
    return decode(cfg, params, pools, entry(logits), len(prompt), n - 1)[0]


def check(params, prompt, entries, conf=CONF) -> dict:
    toks = correct.served_tokens(entries)
    ref = REF.tail_logprobs(params, conf, list(prompt) + toks[:-1], len(toks))
    return correct.compare_probes([(ref, entries)], TOY_FIRST, TOY_DECODED)


def test_prefill_then_24_paged_decode_steps_agree_with_the_reference(model, prompt):
    """The chunked rule, the convolution over q, k and v together, the state
    kernel, partial rotary and the output gate on the paged attention kernel,
    a share of the experts under a live softmax router, over a period and a
    half and a table that opens blocks 3 and 4."""
    cfg, params = model
    out = check(params, prompt, serve(cfg, params, prompt, STEPS + 1))
    assert out["ok"] and out["first_ok"] and out["decoded"]["ok"], out
    assert out["decoded"]["positions"] == STEPS
    assert out["max_abs_diff"] < 5e-3 and out["decoded"]["max_abs_diff"] < 5e-3, out


@pytest.mark.parametrize("chunks,pad", [((17, 17, 6), 0), ((40,), 24), ((32, 8), 8)],
                         ids=["three chunks", "one padded bucket", "two chunks, the last padded"])
def test_a_prompt_prefilled_in_chunks_is_one_prefill(model, prompt, chunks, pad):
    """A chunk after the first goes on from the state and the convolution
    tail the chunk before left and reads the attention keys back from the row
    cache; padding behind the prompt touches neither."""
    cfg, params = model
    whole, (k0, v0) = prefill(cfg, params, prompt)
    parts, (k1, v1) = prefill(cfg, params, prompt, chunks=chunks, pad=pad)
    # the delta rule's conditioning (the limits' comment), on logits up to 40
    np.testing.assert_allclose(parts, whole, atol=5e-3)
    for a, b in zip(k0.st + v0.st, k1.st + v1.st):
        np.testing.assert_allclose(a, b, atol=2e-3, rtol=1e-3)
    out = check(params, prompt, serve(cfg, params, prompt, 4, chunks=chunks, pad=pad))
    assert out["ok"] and out["first_ok"] and out["decoded"]["ok"], out


def _rule_inputs(seed, b, t, h, dk, dv):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    q = gated_delta.l2_normalise(jax.random.normal(ks[0], (b, t, h, dk))) * dk ** -0.5
    k = gated_delta.l2_normalise(jax.random.normal(ks[1], (b, t, h, dk)))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (b, t, h)) * 2)
    s0 = jax.random.normal(ks[4], (b, h, dk, dv))
    return q, k, v, beta, s0, ks[5:]


def test_the_fp8_control_is_far_from_the_reference_with_its_state_in_float32(model, prompt):
    """``--control fp8`` rounds the products' inputs and the cached keys and
    values and leaves the recurrent state in float32, as the configuration
    states it: on the toy the reference's five best log-probabilities a
    position move by a median of 3.6, against limits of 0.02."""
    _, params = model
    toks = list(prompt) + tokens(2, 24)
    ref = REF.tail_logprobs(params, CONF, toks, 24)
    low = REF.tail_logprobs(params, CONF, toks, 24, lower="fp8")
    best = np.argsort(-ref, axis=-1)[:, :correct.TOP_K]
    assert np.median(np.take_along_axis(np.abs(low - ref), best, axis=-1)) > 1.0
    with pytest.raises(ValueError):
        REF.tail_logprobs(params, CONF, toks, 24, lower="int4")


DECAYS = {
    "alpha near 1 (a memory of thousands of tokens)": lambda ks, sh: jnp.full(sh, -1e-4),
    "alpha near 0 (a state that forgets at once)": lambda ks, sh: jnp.full(sh, -30.0),
    "a spread of time constants": lambda ks, sh: -jnp.exp(jax.random.normal(ks[0], sh) * 1.5),
    "heads that keep beside heads that forget, by the token": lambda ks, sh: jnp.where(
        jax.random.bernoulli(ks[1], 0.5, sh), -1e-3, -20.0),
}


@pytest.mark.parametrize("decay", list(DECAYS))
@pytest.mark.parametrize("chunk", [64, 7])
def test_the_chunked_rule_is_the_token_by_token_rule(decay, chunk):
    """150 positions in chunks of 64 (two whole, one padded) and of 7, from a
    state that is not zero, with the last three positions of a row masked out
    (log alpha = 0, beta = 0: neither decays nor writes)."""
    b, t, h, dk, dv = 2, 150, 3, 16, 32
    q, k, v, beta, s0, ks = _rule_inputs(chunk, b, t, h, dk, dv)
    la = DECAYS[decay](ks, (b, t, h)).at[1, -3:].set(0.0)
    beta = beta.at[1, -3:].set(0.0)
    with jax.default_matmul_precision("highest"):
        o0, s_seq = gated_delta.gated_delta_recurrent(q, k, v, la, beta, s0)
        o1, s_chk = jax.jit(gated_delta.gated_delta_chunked, static_argnums=6)(
            q, k, v, la, beta, s0, chunk)
        _, s_short = gated_delta.gated_delta_recurrent(
            q[1:, :-3], k[1:, :-3], v[1:, :-3], la[1:, :-3], beta[1:, :-3], s0[1:])
    # float32 sums in another order and one triangular solve a chunk, on
    # values of size ~1 (outputs) and ~3 (states)
    np.testing.assert_allclose(o1, o0, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(s_chk, s_seq, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(s_chk[1], s_short[0], atol=1e-4, rtol=1e-4)


# one jitted step for every case below: the list is an argument
_STATE_STEP = jax.jit(gated_delta.gated_delta_step_auto)
LIVE_SETS = {"none": [], "slot 0 only": [0], "the last slot only": [5],
             "every other slot": [0, 2, 4], "all": [0, 1, 2, 3, 4, 5]}


def _listed(slots, live):
    return ssm_scan.live_slots(
        jnp.zeros((slots,), bool).at[jnp.asarray(live, jnp.int32)].set(True))


@pytest.mark.parametrize("name", list(LIVE_SETS))
def test_the_state_kernel_is_the_xla_step_on_the_listed_slots_and_no_other(name):
    """The list is data: a listed slot is the XLA step (the rule as written,
    a key head read for the two value heads it serves, the read-out through
    the gated norm), a slot that is not listed keeps its state bit for bit and
    gives zeros, whatever its row of the operands holds (NaN here, z too). The
    first listed row replays a position (alpha 1, beta 0): it reads S^T q and
    keeps its state. Every live set runs the one compiled program, and only
    layer 1 moves."""
    slots, layers, hk, h, dk, dv = 6, 2, 2, 4, 16, 128
    live = LIVE_SETS[name]
    dead = [i for i in range(slots) if i not in live]
    q, k, v, beta, _, ks = _rule_inputs(3, 1, slots, h, dk, dv)
    q, k, v, beta = q[0, :, :hk], k[0, :, :hk], v[0], beta[0]
    pool = jax.random.normal(ks[0], (slots, layers, h, dk, dv))
    decay = jax.nn.sigmoid(jax.random.normal(ks[1], (slots, h)) * 3)
    # z behind the convolution's channels, as the in-projection leaves it
    zs = jax.random.normal(jax.random.PRNGKey(9), (slots, 2 * hk * dk + 2 * h * dv))
    gain = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(10), (layers, 1, dv))
    if live:
        decay, beta = decay.at[live[0]].set(1.0), beta.at[live[0]].set(0.0)
    every = ssm_scan.live_slots(jnp.ones((slots,), bool))
    eps = jnp.full((1,), 1e-6, jnp.float32)
    want, y_want = gated_delta.gated_delta_step_xla(
        pool, 1, every, decay, beta, q, k, gated_delta.Values(v, zs, gain, eps))
    nan = jnp.asarray(dead, jnp.int32)
    decay, beta, q, k, v, zs = (z.at[nan].set(jnp.nan) for z in (decay, beta, q, k, v, zs))
    got, y = _STATE_STEP(pool, 1, _listed(slots, live), decay, beta, q, k,
                         gated_delta.Values(v, zs, gain, eps))
    assert _STATE_STEP._cache_size() == 1
    assert y.shape == (slots, h * dv)
    for i in live:
        np.testing.assert_allclose(got[i], want[i], atol=1e-5)
        np.testing.assert_allclose(y[i], y_want[i], atol=1e-4)
    for i in dead:
        np.testing.assert_array_equal(got[i], pool[i])
        np.testing.assert_array_equal(y[i], np.zeros((h * dv,), np.float32))
    if live:
        np.testing.assert_array_equal(got[live[0]], pool[live[0]])
        assert float(jnp.abs(y[live[0]]).max()) > 0.05
    np.testing.assert_array_equal(got[:, 0], pool[:, 0])  # the other layer untouched


def _one_linear_layer(dtype):
    """A linear layer's decode step at toy size, from the raw input of the
    projections: (cfg, the layer's leaves out of a stack of two, the stack's
    small leaves, h, tails, states); layer 1 is the one that runs."""
    cfg = REF.model_config(CONF, SEQ).with_(dtype=dtype)
    slots, ll = 6, 2
    ks = iter(jax.random.split(jax.random.PRNGKey(17), 12))
    hv, c, vd = cfg.lin_v_heads, cfg.lin_conv_dim, cfg.lin_v_heads * cfg.lin_v_dim
    dt = jnp.dtype(dtype)
    rand = lambda *shape: jax.random.normal(next(ks), shape, jnp.float32)  # noqa: E731
    lin = {"w_qkvz": (rand(ll, cfg.d_model, c + vd) * 0.2).astype(dt),
           "w_ba": rand(ll, cfg.d_model, 2 * hv).astype(dt),
           "conv_w": (rand(ll, cfg.ssm_conv, c) * 0.5).astype(dt),
           "dt_bias": rand(ll, hv).astype(dt), "a_log": (rand(ll, hv) * 0.5).astype(dt),
           "gate_norm": (1 + 0.1 * rand(ll, cfg.lin_v_dim)).astype(dt),
           "w_out": (rand(ll, vd, cfg.d_model) * 0.1).astype(dt)}
    h = rand(slots, 1, cfg.d_model).astype(dt)
    tails = rand(ll, cfg.ssm_conv, slots, c).astype(dt)
    states = rand(slots, ll, hv, cfg.lin_k_dim, cfg.lin_v_dim)
    return cfg, lin, h, tails, states


@jax.jit
def _fused_step(lin, h, tails, states, live, fresh):
    cfg = REF.model_config(CONF, SEQ).with_(dtype=str(h.dtype))
    p = jax.tree.map(lambda a: a[1], lin)
    consts = gated_delta.step_consts(lin, cfg.rms_eps, fresh)
    return gdn_moe.linear_step(h, p, cfg, tails, states, 1, live, consts)


@jax.jit
def _plain_step(lin, h, tails, states, live, fresh):
    cfg = REF.model_config(CONF, SEQ).with_(dtype=str(h.dtype))
    p = jax.tree.map(lambda a: a[1], lin)
    return gdn_moe.linear_step_xla(h, p, cfg, tails, states, 1, live, fresh)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", list(LIVE_SETS))
def test_the_fused_decode_step_of_a_linear_layer_is_the_plain_step(name, dtype):
    """One layer's decode step through ``step_inputs`` and ``gated_delta_step``
    against the plain composition (``conv_step``, ``_split_conv``, ``_gates``,
    the rule a token at a time, the gated norm), from the raw input of the
    projections: the layer's output, the tails and the state agree to float32
    rounding for live and fresh rows (the output to one bf16 step where the
    activations are bf16: both sides round in the same two places); the first
    live row is not fresh: it keeps its tail and its state bit for bit and
    still reads; a slot that is not live keeps both bit for bit and gives
    what ``w_out`` makes of zeros; the other layer's rows do not move."""
    cfg, lin, h, tails, states = _one_linear_layer(dtype)
    slots = h.shape[0]
    live = LIVE_SETS[name]
    dead = [i for i in range(slots) if i not in live]
    mask = jnp.zeros((slots,), bool).at[jnp.asarray(live, jnp.int32)].set(True)
    fresh = mask.at[jnp.asarray(live[:1], jnp.int32)].set(False)
    want, tails_want, states_want = _plain_step(lin, h, tails, states, _listed(slots, live), fresh)
    got, tails_got, states_got = _fused_step(lin, h, tails, states, _listed(slots, live), fresh)
    assert got.shape == want.shape and got.dtype == want.dtype
    tol = 1e-4 if dtype == "float32" else 0.05
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               atol=tol, rtol=tol)
    # a tail row is a copy of a raw input: bit for bit on every row
    np.testing.assert_array_equal(np.asarray(tails_got, np.float32),
                                  np.asarray(tails_want, np.float32))
    np.testing.assert_allclose(states_got, states_want, atol=2e-2 if dtype != "float32" else 1e-4)
    for i in dead + live[:1]:
        np.testing.assert_array_equal(np.asarray(tails_got[:, :, i], np.float32),
                                      np.asarray(tails[:, :, i], np.float32))
        np.testing.assert_array_equal(states_got[i], states[i])
    for i in dead:
        assert not np.asarray(got[i], np.float32).any()
    if live:
        assert float(jnp.abs(got[live[0]].astype(jnp.float32)).max()) > 1e-3  # it still reads
        for i in live[1:]:
            assert np.abs(np.asarray(states_got[i, 1] - states[i, 1])).max() > 1e-3
            assert (np.asarray(tails_got[1, :, i], np.float32)
                    != np.asarray(tails[1, :, i], np.float32)).any()
    np.testing.assert_array_equal(states_got[:, 0], states[:, 0])
    np.testing.assert_array_equal(np.asarray(tails_got[0], np.float32),
                                  np.asarray(tails[0], np.float32))


def test_a_group_admit_of_prompts_of_unequal_length_is_each_alone(model):
    """Rows right-padded to one bucket, ``logit_positions`` their ends: the
    padding runs through neither a row's state nor its convolution tail, and
    a row with no real position in a chunk (its prompt ended in an earlier
    one: -1) keeps what it had."""
    cfg, params = model
    lens = [24, 9, 17]
    prompts = [tokens(10 + i, n) for i, n in enumerate(lens)]
    k, v = llama.make_cache(cfg, 3, SEQ)
    padded = jnp.asarray([p + [0] * (24 - len(p)) for p in prompts], jnp.int32)
    logits, k, v = llama.forward(
        params, cfg, padded, k, v, jnp.zeros((3,), jnp.int32),
        logit_positions=jnp.asarray([n - 1 for n in lens], jnp.int32), fresh_prefill=True)
    # a second chunk in which only row 0 goes on (8 more tokens)
    more = tokens(20, 8)
    logits2, k2, v2 = llama.forward(
        params, cfg, jnp.asarray([more, [0] * 8, [0] * 8], jnp.int32), k, v,
        jnp.full((3,), 24, jnp.int32), logit_positions=jnp.asarray([7, -1, -1], jnp.int32))
    for i, p in enumerate(prompts):
        alone, (ka, va) = prefill(cfg, params, p)
        np.testing.assert_allclose(logits[i, 0], alone, atol=5e-3)
        for row, one, kept in zip(state_row(k, i) + state_row(v, i), ka.st + va.st,
                                  state_row(k2, i) + state_row(v2, i)):
            np.testing.assert_allclose(row, one, atol=2e-3, rtol=1e-3)
            if i:
                np.testing.assert_array_equal(kept, row)
    longer, _ = prefill(cfg, params, prompts[0] + more)
    np.testing.assert_allclose(logits2[0, 0], longer, atol=5e-3)


def test_a_replayed_position_reads_the_state_and_does_not_advance_it(model, prompt):
    """The batcher steps a request with logprobs back onto its last prompt
    position: the state has consumed it already (``seen``)."""
    cfg, params = model
    logits, rows = prefill(cfg, params, prompt)
    pools = into_pool(empty_pools(cfg), rows)
    first = dict(entry(logits), bytes=[prompt[-1]])  # the carry holds prompt[-1] again
    entries, (kp, vp) = decode(cfg, params, pools, first, len(prompt) - 1, 1)
    np.testing.assert_allclose(entries[1]["logprob"], entry(logits)["logprob"], atol=1e-3)
    for before, after in zip(pools, (kp, vp)):
        for a, b in zip(state_row(before, SLOT), state_row(after, SLOT)):
            np.testing.assert_array_equal(a, b)


# -- the share of the experts --------------------------------------------------


def _layer(cfg, params, place):
    moe = params["blocks"]["moe"]
    return ({k: v[place] for k, v in moe.items() if k not in experts.EXPERT_LEAVES},
            tuple(moe[k] for k in experts.EXPERT_LEAVES))


def _uncut_layer(h, small, stacks, top_k):
    """The whole expert layer as the reference's docstring writes it, every
    expert held, in numpy: softmax over all, the ``top_k`` largest
    renormalised, their SwiGLUs, the shared expert behind its sigmoid gate."""
    f = lambda x: np.asarray(x, np.float64)  # noqa: E731
    silu = lambda x: x / (1.0 + np.exp(-x))  # noqa: E731
    wg, wu, wd = (f(s) for s in stacks)
    out = np.zeros_like(f(h))
    for at in np.ndindex(h.shape[:-1]):
        x = f(h)[at]
        logits = x @ f(small["router"])
        p = np.exp(logits - logits.max())
        p /= p.sum()
        picks = np.argsort(-p)[:top_k]
        y = sum(p[e] / p[picks].sum() * ((silu(x @ wg[e]) * (x @ wu[e])) @ wd[e]) for e in picks)
        gate = 1.0 / (1.0 + np.exp(-(x @ f(small["shared_gate"]))))
        out[at] = y + gate * ((silu(x @ f(small["w_gate_s"])) * (x @ f(small["w_up_s"])))
                              @ f(small["w_down_s"]))
    return out


def test_the_four_shares_and_the_shared_expert_once_are_the_uncut_layer(model):
    """The guide's share-sum test: ranks 0-3 of the strided placement hold
    the experts e with e mod 4 == rank of ONE whole layer (32 experts); their
    routed parts summed, with the gated shared expert counted once, are the
    uncut layer, as the program computes it with every expert held and as the
    reference's equations give it (``_uncut_layer``)."""
    cfg, _ = model
    whole_conf = dict(CONF, num_experts=32, expert_parallel={"chips": 1, "rank": 0},
                      num_hidden_layers=4)
    whole_cfg = REF.model_config(whole_conf, SEQ).with_(dtype="float32")
    whole = seeded(whole_cfg)
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 9, cfg.d_model)) * 3
    place = 2
    small, stacks = _layer(whole_cfg, whole, place)
    with jax.default_matmul_precision("highest"):
        want, _ = experts.moe_ffn(h, small | dict(zip(experts.EXPERT_LEAVES,
                                                      (s[place] for s in stacks))), whole_cfg)
        shared, _ = experts.moe_ffn(
            h, small | {k: jnp.zeros_like(s[place]) for k, s in zip(experts.EXPERT_LEAVES, stacks)},
            whole_cfg)
        routed = 0.0
        for rank in range(4):
            c = whole_cfg.with_(moe_ep_size=4, moe_ep_rank=rank)
            held = {k: s[place, rank::4] for k, s in zip(experts.EXPERT_LEAVES, stacks)}
            part, _ = experts.moe_ffn(h, small | held, c)
            routed = routed + (part - shared)
    np.testing.assert_allclose(routed + shared, want, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(
        routed + shared, _uncut_layer(h, small, [s[place] for s in stacks], whole_cfg.n_experts_used),
        atol=1e-4, rtol=1e-4)
    assert float(jnp.abs(want - shared).max()) > 0.05  # the routed part is not nothing


FORMS = ("dense", "hit_list", "grouped")


@pytest.mark.parametrize("rows,why", [(6, "six rows: picks land here, elsewhere and both"),
                                      (1, "one row")])
def test_the_three_forms_agree_for_a_share(model, rows, why):
    cfg, params = model
    small, stacks = _layer(cfg, params, 1)
    h = jax.random.normal(jax.random.PRNGKey(rows), (rows, 1, cfg.d_model)) * 3
    live = jnp.ones((rows,), jnp.float32)
    dense = small | dict(zip(experts.EXPERT_LEAVES, (s[1] for s in stacks)))
    with jax.default_matmul_precision("highest"):
        outs = {f: experts.moe_ffn(h, dense if f == "dense" else small, cfg, live, f, stacks, 1)
                for f in FORMS}
    for f in FORMS[1:]:
        np.testing.assert_allclose(outs[f][0], outs["dense"][0], atol=1e-5, rtol=1e-5)
        np.testing.assert_array_equal(outs[f][1], outs["dense"][1])
    hit, most, n, held = (int(x) for x in outs["dense"][1])
    assert n == rows and 0 <= held <= rows * cfg.n_experts_used and hit <= min(held, 8)


def test_a_step_in_which_no_pick_lands_here_adds_the_shared_expert_alone(model):
    """A router that sends every row to experts 0, 4, 8, 12 (rank 0's): rank
    1 holds none of them. Every form returns the gated shared expert and
    nothing else, the hit list is empty, no picks are held."""
    cfg, params = model
    small, stacks = _layer(cfg, params, 0)
    router = jnp.zeros_like(small["router"]).at[:, jnp.asarray([0, 4, 8, 12])].set(1.0)
    small = dict(small, router=router)
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (3, 1, cfg.d_model)))
    live = jnp.ones((3,), jnp.float32)
    dense = small | dict(zip(experts.EXPERT_LEAVES, (s[0] for s in stacks)))
    none = small | {k: jnp.zeros_like(s[0]) for k, s in zip(experts.EXPERT_LEAVES, stacks)}
    want, _ = experts.moe_ffn(h, none, cfg)
    for f in FORMS:
        got, st = experts.moe_ffn(h, dense if f == "dense" else small, cfg, live, f, stacks, 0)
        np.testing.assert_allclose(got, want, atol=1e-6)
        assert st.tolist() == [0, 0, 3, 0], (f, st)
    assert bool(jnp.all(experts.held_place(jnp.asarray([0, 4, 8, 12]), cfg) == 8))
    assert experts.held_place(jnp.asarray([1, 5, 29]), cfg).tolist() == [0, 1, 7]


def test_the_softmax_router_takes_the_largest_and_renormalises_them(model):
    cfg, params = model
    small, _ = _layer(cfg, params, 3)
    h = jax.random.normal(jax.random.PRNGKey(9), (1, 5, cfg.d_model))
    idx, gate = experts.route(h, small, cfg)
    p = jax.nn.softmax(jnp.einsum("btd,de->bte", h, small["router"],
                                  precision=jax.lax.Precision.HIGHEST), axis=-1)
    want = np.argsort(-np.asarray(p), axis=-1)[..., : cfg.n_experts_used]
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(want, -1))
    np.testing.assert_allclose(jnp.sum(gate, -1), 1.0, atol=1e-6)
    np.testing.assert_allclose(gate, jnp.take_along_axis(p, idx, -1)
                               / jnp.sum(jnp.take_along_axis(p, idx, -1), -1, keepdims=True),
                               atol=1e-6)
    assert p.shape[-1] == 32 and small["router"].shape == (cfg.d_model, 32)


# -- metadata, pricing, refusals -------------------------------------------------


def test_the_metadata_round_trip_keeps_the_family_and_the_share(model):
    from nats_llm_studio_tpu.models.export import config_metadata

    cfg, _ = model
    md = config_metadata(cfg, "m")
    back = ModelConfig.from_gguf_metadata(md).with_(dtype=cfg.dtype)
    assert back == cfg
    assert back.family == "gdn_moe" and back.n_lin_layers == 5 and back.n_kv_layers == 1
    assert back.n_moe_layers == 6 and back.slot_state and back.recurrent and back.kv_pack == 1
    assert (back.n_experts, back.n_experts_held, back.moe_ep_size, back.moe_ep_rank) == (32, 8, 4, 1)
    assert md["qwen3next.full_attention_interval"] == 4
    assert md["qwen3next.attention.norm_zero_centered"] is True


def test_admission_prices_the_state_pool_and_the_experts_held():
    from nats_llm_studio_tpu.parallel.memory import (
        estimate_device_bytes, kv_pool_block_bytes, state_slot_bytes)

    conf = json.loads((ROOT / "benchmark/configs/qwen3-next-80b-a3b-instruct.json").read_text())
    cfg = REF.model_config(conf, 8192)
    layers = conf["num_hidden_layers"]
    lin = layers - layers // 4
    assert (cfg.n_kv_layers, cfg.n_lin_layers, cfg.n_moe_layers) == (layers // 4, lin, layers)
    assert (cfg.n_experts, cfg.n_experts_held, cfg.n_experts_used) == (512, 128, 10)
    # KV of the full layers only: 16 tokens x 2 kv heads x 256 x K and V x bf16
    assert kv_pool_block_bytes(cfg, 16) == (layers // 4) * 2 * 16 * 2 * 256 * 2
    # a slot: a float32 state of 32 x 128 x 128 and 4 bf16 rows of 8,192 channels a layer
    assert state_slot_bytes(cfg) == lin * (32 * 128 * 128 * 4 + 4 * 8192 * 2) + 4
    est = estimate_device_bytes(cfg, {}, batch=32, seq_len=8192)
    per_layer = 128 * 3 * 2048 * 512 + 2048 * 512 + 3 * 2048 * 512   # experts held, router, shared
    mixers = lin * (2048 * 12288 + 2048 * 64 + 4 * 8192 + 4096 * 2048) + (layers // 4) * (
        2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048)
    want = 2 * (layers * per_layer + mixers + 2 * 2048 * conf["vocab_size"])
    assert abs(est["params"] - want) < 1e-3 * want


@pytest.mark.parametrize("how,cause", [
    (dict(paged=False), "paged pool only"),
    (dict(cfg=dict(kv_quant="int8")), "TPU_KV_QUANT=int8 is not implemented for linear-attention"),
    (dict(kv_tiers=object()), "set KV_HOST_POOL_BYTES=0"),
    (dict(env=dict(DECODE_KERNEL="xla")), "decode on the pool in place only"),
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


def test_a_mesh_a_verify_bundle_and_a_gguf_of_tensors_are_refused_with_their_causes(model):
    from nats_llm_studio_tpu.parallel.loader import load_params_sharded
    from nats_llm_studio_tpu.parallel.mesh import build_mesh
    from nats_llm_studio_tpu.parallel.sharding import validate_mesh_for_config

    cfg, params = model
    with pytest.raises(ValueError, match="serve on one chip a replica"):
        validate_mesh_for_config(build_mesh({"tp": 2}, devices=jax.local_devices()[:2]), cfg)
    with pytest.raises(NotImplementedError, match="one position a step"):
        kp, vp = empty_pools(cfg)
        llama.forward_decode_paged(params, cfg, jnp.zeros((SLOTS, 3), jnp.int32), kp, vp,
                                   jnp.zeros((SLOTS, 8), jnp.int32), jnp.zeros((SLOTS,), jnp.int32))
    with pytest.raises(NotImplementedError, match="no GGUF tensor-name map for linear-attention"):
        load_params_sharded(None, cfg, build_mesh({"tp": 1}, devices=jax.local_devices()[:1]))


def test_the_picks_counters_are_on_the_metrics_page():
    from nats_llm_studio_tpu.serve.batcher import BatcherStats

    st = BatcherStats()
    # two layers x three steps of picks held; 24 (row, layer, step) samples of top-10
    assert st.record_picks(24, 10, np.asarray([[5, 6, 7], [2, 2, 3]])) == {
        "moe_picks": 240, "moe_picks_held": 25}
    assert st.record_picks(4, 10) == {"moe_picks": 40, "moe_picks_held": 40}
    assert st.picks_counters() == {"picks": 280, "picks_held": 65}
    text = (ROOT / "nats_llm_studio_tpu/serve/worker.py").read_text()
    assert "picks_counters" in text and "lmstudio_moe_{name}_total" in text
    readme = (ROOT / "README.md").read_text()
    assert "lmstudio_moe_picks_held_total" in readme and "seq/linear" in readme
