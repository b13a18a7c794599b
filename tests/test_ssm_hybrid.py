"""The state-space / attention hybrid family (``models/ssm_hybrid.py``,
``ops/ssm_scan.py``) against its plain reference
(``benchmark/references/ssm_hybrid.py``: the recurrence a token at a time) on
seeded weights, at toy size on the CPU: logits, not tokens. The served side is
driven the way the batcher drives it: ``models.llama.forward`` prefill (whole,
in chunks, or as a padded group) into row caches that carry the rows' state,
written into the pool (KV by table, state by slot), then
``forward_decode_paged`` steps (both Pallas kernels in interpreter mode). The
toy keeps head_dim 64, so two kv heads share a 128-lane cache row as at the
published widths. Faults put in on purpose must each fail the toy limits."""

import asyncio
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import async_test, hold_decodes_until_queued

from benchmark import run
from benchmark.lib import correct, weights
from nats_llm_studio_tpu.models import llama, ssm_hybrid
from nats_llm_studio_tpu.models.config import ModelConfig
from nats_llm_studio_tpu.ops import ssm_scan
from nats_llm_studio_tpu.ops.kvcache import (
    WithState, kv_pool_write_row, kv_pool_zeros, state_row, state_write_row, table_rows_in_use)

ROOT = Path(__file__).resolve().parents[1]
CONF = json.loads((ROOT / "benchmark/tests/rehearsal/configs/tiny-ssm.json").read_text())
REF = run.load_module(ROOT / "benchmark/references/ssm_hybrid.py")

T, SEQ, SLOTS = 16, 128, 3  # pool block tokens; a slot's table spans SEQ
PROMPT = 40                 # not a multiple of T nor of the scan chunk 8 x 3
STEPS = 24
SLOT = 1
TABLE = [3, 5, 2, 7, 1, 4, 6, 8]
# float32 through eight toy layers: the sound path agrees to ~1e-5 (the only
# difference is the order of float32 sums: chunked against sequential, online
# softmax against dense), so the limits sit three orders above it and every
# fault far above them
TOY_FIRST = {"median_tol": 0.02, "token_tol": 0.05}
TOY_DECODED = {"median_tol": 0.02, "token_tol": 0.05, "gap_tol": 0.05}


@pytest.fixture(scope="module")
def model():
    mp = pytest.MonkeyPatch()
    mp.setattr(weights, "INIT_STD", 0.05)   # N(0, 0.02) adds little at d 256
    try:
        from nats_llm_studio_tpu.parallel.mesh import build_mesh

        cfg = REF.model_config(CONF, SEQ).with_(dtype="float32")
        mesh = build_mesh({"tp": 1}, devices=jax.local_devices()[:1])
        # the schema is the reference's param_shapes, the gains its
        # weight_gains, the placement the program's rule for every leaf
        yield cfg, weights.make_seeded_params(4321, REF)(None, cfg, mesh)
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
                 for st, ax in ssm_hybrid.make_state(cfg, SLOTS))


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


def check(params, prompt, entries) -> dict:
    toks = correct.served_tokens(entries)
    ref = REF.tail_logprobs(params, CONF, list(prompt) + toks[:-1], len(toks))
    return correct.compare_probes([(ref, entries)], TOY_FIRST, TOY_DECODED)


def test_prefill_then_24_paged_decode_steps_agree_with_the_reference(model, prompt):
    """The chunked scan (five chunks of 8), packed kv rows, the state kernel
    and the paged attention kernel over a table that opens blocks 3 and 4."""
    cfg, params = model
    out = check(params, prompt, serve(cfg, params, prompt, STEPS + 1))
    assert out["ok"] and out["first_ok"] and out["decoded"]["ok"], out
    assert out["decoded"]["positions"] == STEPS
    assert out["max_abs_diff"] < 1e-3 and out["decoded"]["max_abs_diff"] < 1e-3, out


@pytest.mark.parametrize("chunks,pad", [((17, 17, 6), 0), ((40,), 24), ((32, 8), 8)],
                         ids=["three chunks", "one padded bucket", "two chunks, the last padded"])
def test_a_prompt_prefilled_in_chunks_is_one_prefill(model, prompt, chunks, pad):
    """A chunk after the first goes on from the state and the convolution
    tail the chunk before left and reads the attention keys back from the
    packed row cache; padding behind the prompt touches neither."""
    cfg, params = model
    whole, (k0, v0) = prefill(cfg, params, prompt)
    parts, (k1, v1) = prefill(cfg, params, prompt, chunks=chunks, pad=pad)
    np.testing.assert_allclose(parts, whole, atol=2e-4)
    for a, b in zip(k0.st + v0.st, k1.st + v1.st):
        # float32 sums in another order, on state entries up to ~60
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4)
    out = check(params, prompt, serve(cfg, params, prompt, 4, chunks=chunks, pad=pad))
    assert out["ok"] and out["first_ok"] and out["decoded"]["ok"], out


def ssd_sequential(x, dt, a, bm, cm, s0):
    """The recurrence a position at a time: what ``ssm_scan.ssd_chunked`` is
    checked against (same arguments and results, without ``chunk``)."""
    af = a.astype(jnp.float32)

    def step(s, xs):
        xt, dtt, bt, ct = xs  # [B, H, P], [B, H], [B, N], [B, N]
        s = jnp.exp(dtt * af)[..., None, None] * s + jnp.einsum(
            "bh,bhp,bn->bhpn", dtt, xt, bt)
        return s, jnp.einsum("bhpn,bn->bhp", s, ct)

    s, y = jax.lax.scan(step, s0.astype(jnp.float32), tuple(
        jnp.moveaxis(z.astype(jnp.float32), 1, 0) for z in (x, dt, bm, cm)))
    return jnp.moveaxis(y, 0, 1), s


def state_step_xla(pool, layer, decay, dtx, bm, cm):
    """``ssm_scan.ssm_state_step`` as plain array operations: it slices the
    layer out of the pool and writes it back."""
    k = pool.shape[-1] // dtx.shape[2]
    s = ssm_scan.unpack_state(pool[:, layer], k)  # [slots, H, P, N]
    s = decay.astype(jnp.float32)[..., None, None] * s + jnp.einsum(
        "bhp,bn->bhpn", dtx.astype(jnp.float32), bm.astype(jnp.float32))
    y = jnp.einsum("bhpn,bn->bhp", s, cm.astype(jnp.float32))
    return pool.at[:, layer].set(ssm_scan.pack_state(s, k)), y


@pytest.mark.parametrize("chunk", [8, 5, 16, 7, 40, 64])
def test_the_chunked_scan_is_the_sequential_recurrence(chunk):
    """At chunk lengths that divide the 40 positions (8, 5, 40), that do not
    (16, 7) and that pass them (64), from a state that is not zero, with two
    positions of a row masked out (dt = 0)."""
    b, t, h, p, n = 2, 40, 4, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(chunk), 6)
    x = jax.random.normal(ks[0], (b, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)) * 2).at[1, 37:].set(0.0)
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    bm, cm = jax.random.normal(ks[3], (b, t, n)), jax.random.normal(ks[4], (b, t, n))
    s0 = jax.random.normal(ks[5], (b, h, p, n))
    y0, s_seq = ssd_sequential(x, dt, a, bm, cm, s0)
    y1, s_chk = ssm_scan.ssd_chunked(x, dt, a, bm, cm, s0, chunk)
    # float32 sums in another order: 1e-5 of values of size ~10
    np.testing.assert_allclose(y1, y0, atol=2e-4, rtol=1e-4)
    np.testing.assert_allclose(s_chk, s_seq, atol=2e-4, rtol=1e-4)


def test_the_state_kernel_is_the_xla_step_and_writes_one_layer_in_place():
    slots, layers, h, p, n = 3, 2, 8, 16, 16
    k = ssm_scan.heads_per_row(h, p)
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    pool = jax.random.normal(ks[0], (slots, layers) + ssm_scan.state_plane(h, p, n))
    decay = jax.nn.sigmoid(jax.random.normal(ks[1], (slots, h)))
    dtx = jax.random.normal(ks[2], (slots, h, p))
    bm, cm = jax.random.normal(ks[3], (slots, n)), jax.random.normal(ks[4], (slots, n))
    every = ssm_scan.live_slots(jnp.ones((slots,), bool))
    got, y = ssm_scan.ssm_state_step_auto(pool, 1, every, decay, dtx, bm, cm)
    want, y_want = state_step_xla(pool, 1, decay, dtx, bm, cm)
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(y, y_want, atol=1e-4)
    np.testing.assert_array_equal(got[:, 0], pool[:, 0])  # the other layer untouched
    s = jax.random.normal(ks[0], (2, h, p, n))
    np.testing.assert_array_equal(ssm_scan.unpack_state(ssm_scan.pack_state(s, k), k), s)


# one jitted step for every case below: the list is an argument
_STATE_STEP = jax.jit(ssm_scan.ssm_state_step_auto)
LIVE_SETS = {"none": [], "slot 0 only": [0], "the last slot only": [5],
             "every other slot": [0, 2, 4], "all but one": [0, 1, 2, 4, 5],
             "all": [0, 1, 2, 3, 4, 5]}


@pytest.mark.parametrize("name", list(LIVE_SETS))
def test_the_state_kernel_moves_the_listed_slots_and_no_other(name):
    """The list is data: a listed slot is the XLA step, a slot that is not
    listed keeps its state bit for bit and gives zeros, whatever its row of
    the operands holds (NaN here). The first listed row replays a position
    (decay 1, dt x 0): it reads C . S and keeps its state. Every live set
    runs the one compiled program."""
    slots, layers, h, p, n = 6, 2, 8, 16, 16
    live = LIVE_SETS[name]
    dead = [i for i in range(slots) if i not in live]
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    pool = jax.random.normal(ks[0], (slots, layers) + ssm_scan.state_plane(h, p, n))
    decay = jax.nn.sigmoid(jax.random.normal(ks[1], (slots, h)))
    dtx = jax.random.normal(ks[2], (slots, h, p))
    bm, cm = jax.random.normal(ks[3], (slots, n)), jax.random.normal(ks[4], (slots, n))
    if live:
        decay, dtx = decay.at[live[0]].set(1.0), dtx.at[live[0]].set(0.0)
    want, y_want = state_step_xla(pool, 1, decay, dtx, bm, cm)
    nan = jnp.asarray(dead, jnp.int32)
    decay, dtx, bm, cm = (z.at[nan].set(jnp.nan) for z in (decay, dtx, bm, cm))
    listed = ssm_scan.live_slots(jnp.zeros((slots,), bool).at[jnp.asarray(live, jnp.int32)].set(True))
    assert int(listed.n) == len(live)
    assert listed.order.tolist() == live + [live[-1] if live else 0] * len(dead)
    got, y = _STATE_STEP(pool, 1, listed, decay, dtx, bm, cm)
    assert _STATE_STEP._cache_size() == 1
    for i in live:
        np.testing.assert_allclose(got[i], want[i], atol=1e-5)
        np.testing.assert_allclose(y[i], y_want[i], atol=1e-4)
    for i in dead:
        np.testing.assert_array_equal(got[i], pool[i])
        np.testing.assert_array_equal(y[i], np.zeros((h, p), np.float32))
    if live:
        np.testing.assert_array_equal(got[live[0]], pool[live[0]])
        assert float(jnp.abs(y[live[0]]).max()) > 0.1
    np.testing.assert_array_equal(got[:, 0], pool[:, 0])  # the other layer untouched


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
        np.testing.assert_allclose(logits[i, 0], alone, atol=2e-4)
        for row, one, kept in zip(state_row(k, i) + state_row(v, i), ka.st + va.st,
                                  state_row(k2, i) + state_row(v2, i)):
            np.testing.assert_allclose(row, one, atol=2e-5, rtol=1e-4)
            if i:
                np.testing.assert_array_equal(kept, row)
    longer, _ = prefill(cfg, params, prompts[0] + more)
    np.testing.assert_allclose(logits2[0, 0], longer, atol=2e-4)


def test_a_replayed_position_reads_the_state_and_does_not_advance_it(model, prompt):
    """The batcher steps a request with logprobs back onto its last prompt
    position: the state has consumed it already (``seen``)."""
    cfg, params = model
    logits, rows = prefill(cfg, params, prompt)
    pools = into_pool(empty_pools(cfg), rows)
    first = dict(entry(logits), bytes=[prompt[-1]])  # the carry holds prompt[-1] again
    entries, (kp, vp) = decode(cfg, params, pools, first, len(prompt) - 1, 1)
    np.testing.assert_allclose(entries[1]["logprob"], entry(logits)["logprob"], atol=1e-5)
    # the slot's own rows: the empty slots ride along on token 0 and run it
    # through their (dead) state
    for before, after in zip(pools, (kp, vp)):
        for a, b in zip(state_row(before, SLOT), state_row(after, SLOT)):
            np.testing.assert_array_equal(a, b)


def test_attention_at_head_dim_64_through_packed_rows_is_plain_attention():
    """Two kv heads side by side in a 128-lane row, queries zero-padded onto
    their own head's half, through the paged decode kernel: the XLA reference
    attention over the unpacked keys."""
    from nats_llm_studio_tpu.ops.layers import gqa_attention_hmajor
    from nats_llm_studio_tpu.ops.paged_attention import paged_decode_attention_auto

    cfg = ModelConfig.tiny(n_heads=8, n_kv_heads=4, head_dim=64, d_model=512, n_layers=2,
                           layer_types=("mamba", "attention"), ssm_n_heads=8, ssm_head_dim=16,
                           ssm_d_state=16)
    assert cfg.kv_pack == 2 and cfg.kv_cache_dims() == ((2, 128), (2, 128))
    b, s, nb = 2, 40, 3
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (b, 1, 8, 64))
    k, v = (jax.random.normal(kk, (b, s, 4, 64)) for kk in ks[1:])
    pos = jnp.asarray([s - 1, 20], jnp.int32)
    want = gqa_attention_hmajor(
        q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        jnp.arange(s)[None, None, :] <= pos[:, None, None], 1 / 64)
    tbl = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    pools = []
    for x in (k, v):
        rows = jnp.pad(ssm_hybrid.pack_kv(x, cfg), ((0, 0), (0, nb * T - s), (0, 0), (0, 0)))
        blocks = rows.reshape(b * nb, T, 2, 128).transpose(0, 2, 1, 3)  # [b nb, H', T, 128]
        pools.append(jnp.concatenate([jnp.zeros_like(blocks[:1]), blocks])[:, None])
    got = ssm_hybrid.unpack_o(paged_decode_attention_auto(
        ssm_hybrid.pack_q(q, cfg), pools[0], pools[1], tbl, pos, 0, 1 / 64), cfg)
    np.testing.assert_allclose(got, want, atol=2e-5)


# -- faults put in on purpose ------------------------------------------------


def _another_slots_state(cfg, params, prompt):
    """The slot decodes on the state of a slot that holds another prompt."""
    _, other = prefill(cfg, params, tokens(77, PROMPT))
    logits, rows = prefill(cfg, params, prompt)
    pools = into_pool(into_pool(empty_pools(cfg), rows), other, slot=0)
    kp, vp = pools
    swapped = tuple(
        WithState(p.kv, state_write_row(p, state_row(p, 0), SLOT), p.axes) for p in (kp, vp))
    return decode(cfg, params, swapped, entry(logits), len(prompt), 5)[0]


def _stale_state(cfg, params, prompt):
    """The admit writes the slot's KV and leaves the state of the slot's
    previous request where it was."""
    _, old = prefill(cfg, params, tokens(78, PROMPT))
    logits, rows = prefill(cfg, params, prompt)
    pools = into_pool(into_pool(empty_pools(cfg), old), rows, with_state=False)
    return decode(cfg, params, pools, entry(logits), len(prompt), 5)[0]


def _tail_off_by_one(monkeypatch):
    sound = ssm_scan.causal_conv

    def late(xbc, tail, w, b, valid):
        out, _ = sound(xbc, tail, w, b, valid)
        return out, sound(xbc, tail, w, b, jnp.maximum(valid - 1, 0))[1]

    monkeypatch.setattr(ssm_scan, "causal_conv", late)


def _no_dt_bias(params):
    mamba = dict(params["blocks"]["mamba"])
    mamba["dt_bias"] = jnp.zeros_like(mamba["dt_bias"])
    return dict(params, blocks=dict(params["blocks"], mamba=mamba))


FAULTS = {
    "a slot decodes on another slot's state": dict(serve=_another_slots_state),
    "state left stale from the slot's previous request": dict(serve=_stale_state),
    "the convolution tail off by one": dict(patch=_tail_off_by_one),
    "dt without dt_bias": dict(params=_no_dt_bias),
    "rotary applied to the attention layers": dict(cfg=dict(use_rope=True)),
    "scale 1/8 in place of 1/64": dict(cfg=dict(attention_scale=0.125)),
    "a padded position updates the state": dict(how=dict(pad=24, mask_padding=False)),
}


@pytest.mark.parametrize("name", list(FAULTS))
def test_a_fault_put_in_on_purpose_fails_the_toy_limits(model, prompt, name, monkeypatch):
    cfg, params = model
    how = FAULTS[name]
    if "patch" in how:
        how["patch"](monkeypatch)
    if "serve" in how:
        served = how["serve"](cfg, params, prompt)
    else:
        served = serve(cfg.with_(**how.get("cfg", {})), how.get("params", lambda p: p)(params),
                       prompt, 6, **how.get("how", {}))
    out = check(params, prompt, served)
    d = out["decoded"]
    worst = max(d["median_abs_diff"] / d["median_tolerance"],
                d["max_abs_diff"] / d["token_tolerance"])
    assert not out["ok"] and worst > 5, (name, out)
    print(f"\n{name}: decoded median {d['median_abs_diff']:.3f}, max {d['max_abs_diff']:.3f}")


# -- through the live batcher --------------------------------------------------


def _held_to_the_reference(params, prompt, served):
    """Every served token is the reference's best at its position (float32:
    the margin of a toy's argmax is far over the paths' 1e-5)."""
    ref = REF.tail_logprobs(params, CONF, list(prompt) + served[:-1], len(served))
    gaps = [float(ref[i].max() - ref[i, t]) for i, t in enumerate(served)]
    assert max(gaps) < 1e-3, gaps


@async_test(timeout=240.0)  # every admit and decode program: 44 s beside five workers on an empty compile cache
async def test_two_slots_finish_and_refill_at_different_steps_through_the_live_batcher(model):
    """Five requests of unequal prompts and lengths over two slots: group
    admits, a chunked admit (prompts over the chunk of 16), slots that finish
    and are given to the next request at different steps, every one decoding
    on its own state."""
    from nats_llm_studio_tpu.engine.generator import SamplingParams
    from nats_llm_studio_tpu.serve import batcher as bt
    from nats_llm_studio_tpu.obs import spans

    cfg, params = model
    t0 = time.perf_counter()  # the span ring is the process's: other files' bursts lie before
    reqs = [(tokens(30 + i, n), m) for i, (n, m) in enumerate(
        [(9, 12), (40, 5), (21, 9), (37, 4), (12, 7)])]
    b = bt.ContinuousBatcher(params, cfg, max_slots=2, max_seq_len=SEQ, buckets=[16, 32, 64],
                             prefill_chunk=16, prefix_cache_blocks=8, spec_decode_k=4)
    try:
        assert b.decode_kernel == "pallas" and b.prefix_cache is None and b.spec_cfg is None
        assert set(b.refusals) == {"prefix_cache", "spec_decode"}
        assert "no snapshot" in b.refusals["prefix_cache"]

        async def one(p, m):
            return [t async for t in b.submit(p, SamplingParams(temperature=0.0, max_tokens=m))]

        got = await asyncio.gather(*(one(p, m) for p, m in reqs))
        for (p, m), toks in zip(reqs, got):
            assert len(toks) == m
            _held_to_the_reference(params, p, toks)
        st = b.stats.state_counters()
        assert st["state_steps"] > 0 and st["state_rows"] <= 2 * st["state_steps"]
        assert st["state_admits_fresh"] + st["state_admits_carried"] == len(reqs)
        assert st["state_admits_carried"] == 3  # the prompts over one chunk of 16
        pool = b.pool_stats()["state"]
        assert pool["slots_total"] == 2 and pool["bytes"] == 2 * ssm_hybrid.state_bytes_per_slot(cfg)
        burst = [a for _, _, _, a in spans.records(t0, float("inf"), "batcher.readback")
                 if a and "state_steps" in a]
        assert burst and all(0 < a["state_rows"] <= 2 * a["state_steps"] for a in burst)
        admits = [a for _, _, _, a in spans.records(t0, float("inf"), "batcher.admit") if a]
        assert {a["state"] for a in admits if "state" in a} == {"fresh", "carried"}
    finally:
        b.stop()


@async_test(timeout=240.0)  # 67 s with its fixture beside five workers on an empty compile cache
async def test_a_finished_and_a_reserved_slot_keep_their_state_across_a_burst(model):
    """Three slots. A decodes throughout; B and E finish early and leave their
    last state in slots 1 and 2; C, a prompt of three chunks, then reserves
    slot 1 while A's bursts go on between its chunks. Every decode launch is
    held to: the rows of its table that name a block are the slots that hold
    a decoding request (the device lists by that rule, the host counts by it),
    and the state, tail and ``seen`` of every other slot come out bit for bit
    as they went in. C then decodes in the freed slot as the reference does."""

    from nats_llm_studio_tpu.engine.generator import SamplingParams
    from nats_llm_studio_tpu.obs import spans
    from nats_llm_studio_tpu.serve import batcher as bt

    cfg, params = model
    pa, pb, pe, pc = tokens(60, 9), tokens(61, 11), tokens(62, 10), tokens(63, 40)
    b = bt.ContinuousBatcher(params, cfg, max_slots=3, max_seq_len=SEQ, buckets=[16, 32, 64],
                             prefill_chunk=16)
    # (what each slot held, its rows before, its rows after, traces so far)
    launches = []
    traced = []     # the burst program's traces
    held = {"released": False}

    def rows_of(kp, vp):
        return [[np.array(leaf, copy=True) for leaf in state_row(kp, i) + state_row(vp, i)]
                for i in range(3)]

    def watched(fn):
        def run(*args, **kwargs):
            slots = list(b._slots)
            if not held["released"] and slots[1] is None and slots[2] is None:
                end = time.monotonic() + 30.0   # C arrives while A still decodes
                while b._inbox.qsize() == 0 and time.monotonic() < end:
                    time.sleep(0.001)
                held["released"] = True
            kinds = ["live" if isinstance(r, bt._Request) else
                     "reserved" if r is bt._RESERVED else "empty" for r in slots]
            assert table_rows_in_use(np.asarray(args[4])).tolist() == [k == "live" for k in kinds]
            before = rows_of(args[2], args[3])   # the pools are donated: copies
            out = fn(*args, **kwargs)
            launches.append((kinds, before, rows_of(out[1], out[2]), len(traced)))
            return out
        return run

    b._decode_pallas = watched(b._decode_pallas)

    def on_duration(event, seconds, **kw):
        if event.endswith("jaxpr_trace_duration") and kw.get("fun_name") == "decode_pos_pallas":
            traced.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    t0 = time.perf_counter()
    try:
        async def one(p, m):
            return [t async for t in b.submit(p, SamplingParams(temperature=0.0, max_tokens=m))]

        ta = asyncio.ensure_future(one(pa, 70))
        got_b, got_e = await asyncio.gather(one(pb, 3), one(pe, 4))
        got_c = await one(pc, 6)
        got_a = await ta
        for p, toks, m in ((pa, got_a, 70), (pb, got_b, 3), (pe, got_e, 4), (pc, got_c, 6)):
            assert len(toks) == m
            _held_to_the_reference(params, p, toks)
        kept = {"reserved": 0, "empty": 0}
        for kinds, before, after, _ in launches:
            for i, kind in enumerate(kinds):
                if kind != "live":
                    for x, y in zip(before[i], after[i]):
                        np.testing.assert_array_equal(x, y)
                    # a state some request left there, not the pool's zeros
                    kept[kind] += bool(np.any(before[i][-1] != 0))
        sets = [tuple(k) for k, *_ in launches]
        assert kept["reserved"] >= 1 and kept["empty"] >= 1, sets
        # one program for every live set: the first two launches (all three
        # slots live; their position carries uploaded, then carried) build
        # what there is to build, and no other live set builds anything
        assert sets[0] == sets[1] and len(set(sets)) >= 4
        assert len(traced) == launches[1][3] <= 2, [n for *_, n in launches]
        burst = [a for _, _, _, a in spans.records(t0, float("inf"), "batcher.readback")
                 if a and "state_steps" in a]
        assert len(burst) == len(launches)
        assert all(a["state_slots_moved"] == a["state_rows"] for a in burst)
        assert any(a["state_slots_moved"] < 3 * a["state_steps"] for a in burst)
        st = b.stats.state_counters()
        assert 0 < st["state_slots_moved"] == st["state_rows"] < 3 * st["state_steps"]
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        b.stop()


@async_test
async def test_a_request_with_logprobs_replays_its_last_prompt_position(model, prompt):
    """The ext path: the admit's token is dropped and the last prompt position
    decoded again under the mask; the state must not consume it twice."""
    from nats_llm_studio_tpu.engine.generator import SamplingParams
    from nats_llm_studio_tpu.serve import batcher as bt

    cfg, params = model
    b = bt.ContinuousBatcher(params, cfg, max_slots=2, max_seq_len=SEQ, buckets=[16, 32, 64],
                             prefill_chunk=16)
    try:
        sp = SamplingParams(temperature=0.0, max_tokens=6)
        toks = [t[0] async for t in b.submit(prompt, sp, want_logprobs=True, top_logprobs=3)]
        _held_to_the_reference(params, prompt, toks)
    finally:
        b.stop()


@async_test(timeout=240.0)  # two batchers' programs: 46 s alone on an empty compile cache
async def test_a_preempted_slot_resumes_on_its_own_state_and_kv(model):
    """QoS preempt-and-resume (``tests/test_qos.py``'s geometry: a pool of
    three blocks of 32, one step a dispatch): a premium admit parks the batch
    slot, its KV blocks AND its state row go to the host, another request
    runs through the slot's neighbour, and the victim's tokens after the
    resume are the reference's and those of a run that was never parked."""
    from nats_llm_studio_tpu.engine.generator import SamplingParams
    from nats_llm_studio_tpu.serve import batcher as bt

    cfg, params = model
    pa, pb = tokens(50, 33), tokens(51, 40)
    kw = dict(max_slots=2, max_seq_len=SEQ, buckets=[16, 32, 64], prefill_chunk=32,
              kv_block_tokens=32, decode_burst=1, admit_coalesce_ms=0.0, qos_preempt=True)

    async def one(b, p, m, **who):
        return [t async for t in b.submit(p, SamplingParams(temperature=0.0, max_tokens=m), **who)]

    ample = bt.ContinuousBatcher(params, cfg, **kw)
    try:
        want_a, want_b = await one(ample, pa, 12), await one(ample, pb, 8)
    finally:
        ample.stop()
    b = bt.ContinuousBatcher(params, cfg, kv_pool_blocks=3, **kw)
    try:
        hold_decodes_until_queued(b)   # A cannot finish before B has arrived
        started = asyncio.get_running_loop().create_future()

        async def run_a():
            out = []
            async for t in b.submit(pa, SamplingParams(temperature=0.0, max_tokens=12),
                                    tenant="hobby", priority="batch"):
                out.append(t)
                if len(out) == 2 and not started.done():
                    started.set_result(None)
            return out

        ta = asyncio.ensure_future(run_a())
        await started
        got_b = await one(b, pb, 8, tenant="acme", priority="premium")
        got_a = await ta
        assert b._suspend_stats["suspended_total"] >= 1 and b._suspend_stats["resumed_total"] >= 1
        assert got_a == want_a and got_b == want_b
        _held_to_the_reference(params, pa, got_a)
        _held_to_the_reference(params, pb, got_b)
    finally:
        b.stop()


# -- metadata, pricing, refusals -------------------------------------------------


def test_the_metadata_round_trip_keeps_the_family(model):
    from nats_llm_studio_tpu.models.export import config_metadata

    cfg, _ = model
    back = ModelConfig.from_gguf_metadata(config_metadata(cfg, "m")).with_(
        dtype=cfg.dtype, tie_embeddings=cfg.tie_embeddings)
    assert back == cfg
    assert back.family == "ssm_hybrid" and back.n_ssm_layers == 6 and back.n_kv_layers == 2
    assert not back.use_rope and back.kv_pack == 2
    assert back.kv_cache_dims() == ((1, 128), (1, 128))   # two kv heads of 64 a row


def test_admission_prices_the_state_pool_beside_the_kv_pool():
    from nats_llm_studio_tpu.parallel.memory import (
        estimate_device_bytes, kv_pool_block_bytes, state_slot_bytes)

    conf = json.loads((ROOT / "benchmark/configs/granite-4.0-h-micro.json").read_text())
    cfg = REF.model_config(conf, 4096)
    assert cfg.n_kv_layers == 4 and cfg.n_ssm_layers == 36
    # KV of 4 layers, not 40: 16 tokens x 8 kv heads x 64 x K and V x bf16
    assert kv_pool_block_bytes(cfg, 16) == 4 * 2 * 16 * 8 * 64 * 2
    # a slot: 36 x (64 x 64 x 128 float32 + 4 x 4352 bf16) + seen
    assert state_slot_bytes(cfg) == 36 * (64 * 64 * 128 * 4 + 4 * 4352 * 2) + 4
    est = estimate_device_bytes(cfg, {}, batch=32, seq_len=4096)
    assert 6.7e9 < est["params"] < 6.9e9            # 3.19 B parameters + the untied head
    assert est["kv_cache"] == 32 * 4096 * 4 * 2 * 8 * 64 * 2 + 32 * state_slot_bytes(cfg)


@pytest.mark.parametrize("how,cause", [
    (dict(paged=False), "paged pool only"),
    (dict(cfg=dict(kv_quant="int8")), "TPU_KV_QUANT=int8 is not implemented for state-space"),
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
    with pytest.raises(NotImplementedError, match="no GGUF tensor-name map for state-space"):
        load_params_sharded(None, cfg, build_mesh({"tp": 1}, devices=jax.local_devices()[:1]))
    b = ContinuousBatcher(params, cfg, max_slots=2)
    try:
        with pytest.raises(ValueError, match="KVX1 carries KV blocks and no recurrent state"):
            b.export_prefix_blocks([1, 2, 3])
    finally:
        b.stop()


def test_the_refusals_and_the_state_pool_are_on_the_metrics_page():
    from nats_llm_studio_tpu.serve.batcher import BatcherStats

    st = BatcherStats()
    assert st.record_state(24, 8, 27) == {"state_rows": 192, "state_steps": 8,
                                          "state_slots_moved": 216}
    assert st.record_state(3, 1, 3) == {"state_rows": 3, "state_steps": 1, "state_slots_moved": 3}
    counters = st.state_counters()
    assert counters["state_rows"] == 195 and counters["state_steps"] == 9
    assert counters["state_slots_moved"] == 219
    # the page shows every key of state_counters() as lmstudio_ssm_<key>_total
    assert "state_slots_moved" in (ROOT / "README.md").read_text()
    text = (ROOT / "nats_llm_studio_tpu/serve/worker.py").read_text()
    for name in ("lmstudio_ssm_{name}_total", "lmstudio_ssm_state_pool_bytes",
                 "lmstudio_feature_refused"):
        assert name in text


# -- the tails a tap a plane: the parent's arithmetic, another place in memory --

NEMO = json.loads((ROOT / "benchmark/tests/rehearsal/configs/tiny-nemotron.json").read_text())
REF_GROUPS = run.load_module(ROOT / "benchmark/references/ssm_latent_moe.py")
PARENT_STREAMS = ROOT / "tests/ssm_hybrid_parent_streams.json"
GROUPS = [1, 8]


def grouped_config(groups: int) -> ModelConfig:
    """One group: ``tiny-ssm`` (8 heads of 16, conv_dim 160). Eight: the toy
    of ``nemotron_h`` (one-sublayer layers, a live router) at 16 heads of 64
    in 8 groups, a state row a group, conv_dim 1,280."""
    if groups == 1:
        return REF.model_config(CONF, SEQ).with_(dtype="float32")
    hf = dict(NEMO, n_groups=groups, mamba_num_heads=2 * groups)
    return REF_GROUPS.model_config(hf, SEQ).with_(dtype="float32")


def grouped_model(groups: int):
    """(cfg, seeded parameters) of ``grouped_config``."""
    from nats_llm_studio_tpu.parallel.mesh import build_mesh

    cfg = grouped_config(groups)
    mp = pytest.MonkeyPatch()
    mp.setattr(weights, "INIT_STD", 0.05 if groups == 1 else 0.08)
    try:
        mesh = build_mesh({"tp": 1}, devices=jax.local_devices()[:1])
        return cfg, weights.make_seeded_params(
            4321, REF if groups == 1 else REF_GROUPS)(None, cfg, mesh)
    finally:
        mp.undo()


def grouped_stream(groups: int) -> list[dict]:
    """The served greedy stream the fixture holds: a prompt of 40 in two
    chunks (the second carries state and tail, and is padded by 8), its state
    written into slot 1, then 24 paged decode steps. Each position: the token
    and its top-5 (ids, log-probabilities)."""
    cfg, params = grouped_model(groups)
    served = serve(cfg, params, tokens(1, PROMPT), STEPS + 1, chunks=(32, 8), pad=8)
    return [{"token": e["bytes"][0], "top": [t["bytes"][0] for t in e["top_logprobs"]],
             "logprobs": [t["logprob"] for t in e["top_logprobs"]]} for e in served]


def parent_conv_step(xbc, tail, w, b, fresh):
    """``ssm_scan.conv_step`` as the parent commit had it, ``tail`` [B, K, C]."""
    shifted = jnp.concatenate([tail[:, 1:], xbc[:, None].astype(tail.dtype)], axis=1)
    tail = jnp.where(fresh[:, None, None], shifted, tail)
    out = jnp.sum(w.astype(jnp.float32)[None] * tail.astype(jnp.float32), axis=1)
    if b is not None:
        out = b.astype(jnp.float32) + out
    return jax.nn.silu(out).astype(xbc.dtype), tail


def parent_causal_conv(xbc, tail, w, b, valid):
    """``ssm_scan.causal_conv`` as the parent commit had it, ``tail`` [B, K, C]."""
    k, t = w.shape[0], xbc.shape[1]
    ext = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
    wf = w.astype(jnp.float32)
    out = sum(wf[j] * ext[:, 1 + j: 1 + j + t].astype(jnp.float32) for j in range(k))
    if b is not None:
        out = b.astype(jnp.float32) + out
    new_tail = jax.vmap(lambda e, v: jax.lax.dynamic_slice_in_dim(e, v, k, axis=0))(ext, valid)
    return jax.nn.silu(out).astype(xbc.dtype), new_tail.astype(tail.dtype)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("groups", GROUPS)
def test_the_convolution_a_tap_a_plane_is_the_parents_row_major_one(groups, dtype):
    """``conv_step`` and ``causal_conv`` on ``tail`` [K, B, C] against the
    parent's formulas on [B, K, C], written out above: the same sums in the
    same order, so equal to float32 round-off, and the tails bit for bit.
    The step: rows that consume their position, a live row that replays its
    last one (``start_pos < seen``) and a row that is not live, as
    ``forward_decode_paged`` makes ``fresh``. The prefill: a ``valid`` of
    every length from 0 (the old tail is kept) through the K - 1 that leave
    part of the old tail in the new one, up to all T."""
    cfg = grouped_config(groups)
    k, c, dt = cfg.ssm_conv, cfg.ssm_conv_dim, jnp.dtype(dtype)
    assert c == cfg.ssm_d_inner + 2 * groups * cfg.ssm_d_state
    ks = jax.random.split(jax.random.PRNGKey(groups), 6)
    w, b = jax.random.normal(ks[0], (k, c)).astype(dt), jax.random.normal(ks[1], (c,)).astype(dt)
    rows = 6
    live = jnp.asarray([True, True, False, True, False, True])
    start, seen = jnp.asarray([7, 4, 9, 0, 0, 12]), jnp.asarray([7, 5, 3, 0, 0, 12])
    fresh = live & (start >= seen)
    assert fresh.tolist() == [True, False, False, True, False, True]
    tail = jax.random.normal(ks[2], (rows, k, c)).astype(dt)
    planes = jnp.swapaxes(tail, 0, 1)
    x = jax.random.normal(ks[3], (rows, c)).astype(dt)
    t = 6
    valid = jnp.asarray([0, 1, 2, 3, t - 1, t])
    xs = jax.random.normal(ks[4], (rows, t, c)).astype(dt)
    for new, parent, args in ((ssm_scan.conv_step, parent_conv_step, (x, fresh)),
                              (ssm_scan.causal_conv, parent_causal_conv, (xs, valid))):
        for bias in (b, None):
            want, want_tail = parent(args[0], tail, w, bias, args[1])
            got, got_tail = jax.jit(new)(args[0], planes, w, bias, args[1])
            assert got_tail.shape == (k, rows, c) and got_tail.dtype == dt and got.dtype == dt
            np.testing.assert_array_equal(jnp.swapaxes(got_tail, 0, 1), want_tail)
            np.testing.assert_allclose(got.astype(jnp.float32), want.astype(jnp.float32),
                                       rtol=1e-6, atol=1e-6)
    # the prefill's last case, row by row of ``valid``
    np.testing.assert_array_equal(got_tail[:, 0], tail[0])          # no real position
    np.testing.assert_array_equal(got_tail[:-2, 2], tail[2, 2:])    # two of the old, two new


@pytest.mark.parametrize("groups", GROUPS)
def test_a_served_stream_is_the_stream_the_parent_commit_served(groups):
    """``tests/ssm_hybrid_parent_streams.json`` was recorded with
    ``grouped_stream`` from the PARENT commit (948cf99, tails [Lm, rows, K,
    C]) before ``state_shapes``, ``conv_step`` and ``causal_conv`` were
    edited: a chunked, padded prefill and 24 decode steps at one group and at
    eight (B, C and the gated norm a group's, one-sublayer layers, a live
    router). Where a tap lies in memory changes no arithmetic: the tokens are
    the parent's and the top-5 log-probabilities its to float32 round-off.
    The check that ``correct``'s wide limits could not give PR 56."""
    want = json.loads(PARENT_STREAMS.read_text())[str(groups)]
    got = grouped_stream(groups)
    assert len(got) == len(want) == STEPS + 1
    assert [e["token"] for e in got] == [e["token"] for e in want]
    assert [e["top"] for e in got] == [e["top"] for e in want]
    np.testing.assert_allclose([e["logprobs"] for e in got], [e["logprobs"] for e in want],
                               rtol=0, atol=1e-5)


def test_the_tails_lie_a_tap_a_plane_and_a_row_moves_by_its_axes(model):
    cfg, _ = model
    (tail, seen), (plane,) = ssm_hybrid.state_shapes(cfg, SLOTS)
    assert tail == (cfg.n_ssm_layers, cfg.ssm_conv, SLOTS, cfg.ssm_conv_dim) and seen == (SLOTS,)
    (k_st, k_axes), (v_st, v_axes) = ssm_hybrid.make_state(cfg, SLOTS)
    assert k_axes == ssm_hybrid.K_AXES == (2, 0) and v_axes == (0,)
    assert [a.shape[ax] for a, ax in zip(k_st + v_st, k_axes + v_axes)] == [SLOTS] * 3
    assert ssm_hybrid.state_bytes_per_slot(cfg) * SLOTS == sum(a.nbytes for a in k_st + v_st)
