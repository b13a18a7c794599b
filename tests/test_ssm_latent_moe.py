"""The state-space family with a layer ONE sublayer (``models/ssm_hybrid.py``
with ``"experts"`` among its kinds: Mamba-2 of several groups, NoPE attention,
two-matrix relu^2 experts in a latent of which the chip holds a share:
``models/experts.py``, ``ops/moe_experts.py``, ``ops/ssm_scan.py``) against its
plain reference (``benchmark/references/ssm_latent_moe.py``: the recurrence a
token at a time, the experts a gather over the held ones) on seeded weights
with a LIVE router, at toy size on the CPU: logits, not tokens. The served side
is driven the way the batcher drives it: ``models.llama.forward`` prefill
(whole, in chunks, or as a padded bucket) into row caches that carry the rows'
state, written into the pool (KV by table, state by slot), then
``forward_decode_paged`` steps (the state kernel at two groups, the paged
attention kernel and the expert kernels in interpreter mode). The toy is
``MEME*ME`` (a pair the plan scans twice, the attention layer, a mixer and an
expert layer on their own), holds 8 of 32 experts (rank 1 of 4). Faults put in
on purpose are in ``tests/test_ssm_latent_moe_faults.py``, the live batcher in
``tests/test_ssm_latent_moe_served.py``."""

import json
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run
from benchmark.lib import correct, weights
from nats_llm_studio_tpu.models import experts, llama, ssm_hybrid
from nats_llm_studio_tpu.models.config import ModelConfig
from nats_llm_studio_tpu.ops import moe_experts, ssm_scan
from nats_llm_studio_tpu.ops.kvcache import (
    WithState, kv_pool_write_row, kv_pool_zeros, state_write_row)

ROOT = Path(__file__).resolve().parents[1]
CONF = json.loads((ROOT / "benchmark/tests/rehearsal/configs/tiny-nemotron.json").read_text())
REF = run.load_module(ROOT / "benchmark/references/ssm_latent_moe.py")

T, SEQ, SLOTS = 16, 128, 3  # pool block tokens; a slot's table spans SEQ
PROMPT = 40                 # not a multiple of T nor of the scan's chunk of 16
STEPS = 24
SLOT = 1
TABLE = [3, 5, 2, 7, 1, 4, 6, 8]
# float32 through seven toy layers: the sound path reads a median of 1e-5 and
# at most 1.3e-4 on logits of size ~30 (the chunked scan's sums in another
# order than the token-by-token recurrence's, exp of differences of cumulative
# sums against products of decays). The limits sit an order above that; a
# router computed from bfloat16 operands (8 mantissa bits: its gates move by
# 4e-3 relative and a near-tied pick flips) reads a median of 1e-3 and single
# positions of 3e-2, a state carried in bfloat16 more, and every fault far over.
# A FIRST position is five values (one position's top-5), so its median is
# no average: out of a padded bucket one seed of three reads 3.0e-4 and at
# most 6.8e-4 there (the others 4e-6 to 8e-5), hence the wider pair
TOY_FIRST = {"median_tol": 5e-4, "token_tol": 2e-3}
TOY_DECODED = {"median_tol": 2e-4, "token_tol": 1e-3, "gap_tol": 1e-3}


def seeded(cfg, gains=None, seed=4321):
    from nats_llm_studio_tpu.parallel.mesh import build_mesh

    mesh = build_mesh({"tp": 1}, devices=jax.local_devices()[:1])
    family = types.SimpleNamespace(param_shapes=REF.param_shapes,
                                   weight_gains=gains or REF.weight_gains)
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
                 for st, ax in ssm_hybrid.make_state(cfg, SLOTS))


def prefill(cfg, params, prompt, chunks=None, pad=0):
    """``prompt`` into a fresh row cache, in ``chunks``, the last chunk
    right-padded by ``pad`` positions as an admit bucket pads it. Returns
    (the logits after the prompt's last position, the row caches)."""
    k, v = llama.make_cache(cfg, 1, SEQ)
    at = 0
    for c in chunks or (len(prompt),):
        last = at + c == len(prompt)
        toks = prompt[at: at + c] + [0] * (pad if last else 0)
        logits, k, v = llama.forward(
            params, cfg, jnp.asarray([toks], jnp.int32), k, v, jnp.asarray([at], jnp.int32),
            logit_positions=jnp.asarray([c - 1], jnp.int32), fresh_prefill=at == 0)
        at += c
    return logits[0, -1], (k, v)


def into_pool(pools, rows, slot=SLOT):
    """A prefilled row's KV into the table's blocks and its state into the
    slot's row: what ``serve/programs.py pool_write`` does."""
    bids = jnp.asarray(TABLE, jnp.int32)
    return tuple(WithState(kv_pool_write_row(p.kv, r.kv, bids), state_write_row(p, r.st, slot),
                           p.axes) for p, r in zip(pools, rows))


def decode(cfg, params, pools, first, pos, n, slot=SLOT):
    """n greedy steps of ``slot`` through the paged decode path; the other
    slots ride along on token 0, as empty slots do. Returns (entries, pools,
    the last step's expert counters)."""
    tbl = np.zeros((SLOTS, len(TABLE)), np.int32)
    tbl[slot] = TABLE
    tbl = jnp.asarray(tbl)
    step = jax.jit(lambda tok, kp, vp, pos: llama.forward_decode_paged(
        params, cfg, tok, kp, vp, tbl, pos, moe_stats=True))
    entries, stats = [first], None
    kp, vp = pools
    for i in range(n):
        tok = np.zeros((SLOTS, 1), np.int32)
        tok[slot] = entries[-1]["bytes"][0]
        at = np.zeros((SLOTS,), np.int32)
        at[slot] = pos + i
        logits, kp, vp, stats = step(jnp.asarray(tok), kp, vp, jnp.asarray(at))
        entries.append(entry(logits[slot, -1]))
    return entries, (kp, vp), stats


def serve(cfg, params, prompt, n, **how):
    logits, rows = prefill(cfg, params, prompt, **how)
    pools = into_pool(empty_pools(cfg), rows)
    return decode(cfg, params, pools, entry(logits), len(prompt), n - 1)[0]


def check(params, prompt, entries, conf=CONF) -> dict:
    toks = correct.served_tokens(entries)
    ref = REF.tail_logprobs(params, conf, list(prompt) + toks[:-1], len(toks))
    return correct.compare_probes([(ref, entries)], TOY_FIRST, TOY_DECODED)


def test_prefill_then_24_paged_decode_steps_agree_with_the_reference(model, prompt):
    """The chunked scan at two groups, the convolution over x, B and C, the
    state kernel at two groups, NoPE attention on the paged kernel, a share of
    two-matrix experts in a latent under a live sigmoid router, over a scanned
    pair of kinds and a table that opens blocks 3 and 4."""
    cfg, params = model
    out = check(params, prompt, serve(cfg, params, prompt, STEPS + 1))
    assert out["ok"] and out["first_ok"] and out["decoded"]["ok"], out
    assert out["decoded"]["positions"] == STEPS
    assert out["max_abs_diff"] < 5e-4 and out["decoded"]["max_abs_diff"] < 5e-4, out


@pytest.mark.parametrize("what", ["state", "router"])
def test_a_state_or_a_router_in_bfloat16_fails_the_toy_limits(model, prompt, what, monkeypatch):
    """What the limits are tight for: the state a chunk hands the next and a
    step reads rounded to bfloat16, or the router's scores computed from
    bfloat16 operands."""
    cfg, params = model
    if what == "state":
        sound = ssm_scan.ssd_chunked

        def rounded(*a):
            y, s = sound(*a)
            return y, s.astype(jnp.bfloat16).astype(jnp.float32)

        monkeypatch.setattr(ssm_scan, "ssd_chunked", rounded)
    else:
        sound_route = experts.route

        def low(h, p, c):
            return sound_route(h.astype(jnp.bfloat16), dict(
                p, router=p["router"].astype(jnp.bfloat16).astype(jnp.float32)), c)

        monkeypatch.setattr(experts, "route", low)
    served = serve(cfg, params, prompt, 8, chunks=(17, 17, 6))
    monkeypatch.undo()
    out = check(params, prompt, served)
    assert not out["ok"], out


@pytest.mark.parametrize("chunks,pad", [((17, 17, 6), 0), ((40,), 24), ((32, 8), 8)],
                         ids=["three chunks", "one padded bucket", "two chunks, the last padded"])
def test_a_prompt_prefilled_in_chunks_is_one_prefill(model, prompt, chunks, pad):
    """A chunk after the first goes on from the state and the convolution
    tail the chunk before left (17 is no multiple of the scan's chunk of 16:
    an admit's edge inside a scan chunk) and reads the attention keys back from
    the row cache; padding behind the prompt touches neither."""
    cfg, params = model
    whole, (k0, v0) = prefill(cfg, params, prompt)
    parts, (k1, v1) = prefill(cfg, params, prompt, chunks=chunks, pad=pad)
    # float32 sums in another order, on logits up to ~30
    np.testing.assert_allclose(parts, whole, atol=1e-3)
    # a state's entries run to ~50 (dt to 4 on inputs of size ~3): float32
    # sums cut at another place
    for a, b in zip(k0.st + v0.st, k1.st + v1.st):
        np.testing.assert_allclose(a, b, atol=2e-3, rtol=1e-3)
    out = check(params, prompt, serve(cfg, params, prompt, 4, chunks=chunks, pad=pad))
    assert out["ok"] and out["first_ok"] and out["decoded"]["ok"], out


def test_the_fp8_control_is_far_from_the_reference(model, prompt):
    _, params = model
    toks = list(prompt) + tokens(2, 24)
    ref = REF.tail_logprobs(params, CONF, toks, 24)
    low = REF.tail_logprobs(params, CONF, toks, 24, lower="fp8")
    best = np.argsort(-ref, axis=-1)[:, :correct.TOP_K]
    assert np.median(np.take_along_axis(np.abs(low - ref), best, axis=-1)) > 0.1
    with pytest.raises(ValueError):
        REF.tail_logprobs(params, CONF, toks, 24, lower="int4")


# -- the scan and the state kernel at several groups -----------------------------


def _scan_inputs(seed, b, t, h, p, n, g):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (b, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    bm, cm = (jax.random.normal(k, (b, t, g, n)) for k in ks[3:5])
    return x, dt, a, bm, cm, jax.random.normal(ks[5], (b, h, p, n))


def test_one_group_is_the_path_the_one_group_models_run_bit_for_bit():
    """``n_groups`` 1 (granite-4.0-h-micro): B and C without a group axis take
    the scan and the kernel as they were, and the plan of granite's layers is
    the runs of one kind it was. A group axis of one gives the same numbers."""
    x, dt, a, bm, cm, s0 = _scan_inputs(0, 2, 40, 4, 64, 16, 1)
    y0, s_end0 = ssm_scan.ssd_chunked(x, dt, a, bm[:, :, 0], cm[:, :, 0], s0, 16)
    y1, s_end1 = ssm_scan.ssd_chunked(x, dt, a, bm, cm, s0, 16)
    np.testing.assert_array_equal(y0, y1)
    np.testing.assert_array_equal(s_end0, s_end1)
    granite = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    assert ssm_hybrid.period_runs(granite) == [
        (("mamba",), 5, 0), (("attention",), 1, 5), (("mamba",), 4, 6)]
    assert ssm_hybrid.period_runs(("linear",) * 3 + ("attention",)) == [
        (("linear",), 3, 0), (("attention",), 1, 3)]
    assert ssm_hybrid.period_runs(tuple(REF.layer_kinds(
        {"hybrid_override_pattern": "MEMEMEM*EME"}))) == [
        (("mamba", "experts"), 3, 0), (("mamba",), 1, 6), (("attention",), 1, 7),
        (("experts",), 1, 8), (("mamba",), 1, 9), (("experts",), 1, 10)]
    cfg = ModelConfig.tiny(layer_types=granite, n_layers=10, ssm_n_heads=4, ssm_head_dim=16,
                           ssm_d_state=8)
    x4 = jnp.ones((2, 4 * 16 + 16))
    assert ssm_hybrid._split_conv(x4, cfg)[1].shape == (2, 8)  # no group axis


def test_a_scanned_pair_hands_the_ffn_each_layers_own_place_in_the_model():
    """A caller's ``ffn`` (``models/gdn_moe.py``, ``models/sala.py``) indexes
    its expert stack by ``place``: in a run of a PAIR of kinds the two layers
    of a turn are two places, in model order."""
    kinds = ("mamba", "attention") * 3 + ("mamba",)
    assert ssm_hybrid.period_runs(kinds) == [(("mamba", "attention"), 3, 0), (("mamba",), 1, 6)]
    cfg = ModelConfig.tiny(layer_types=kinds * 2, n_layers=14)
    params = {"blocks": {"mamba": {"mix_norm": jnp.ones((8, cfg.d_model))},
                         "attn": {"mix_norm": jnp.ones((6, cfg.d_model))}}}

    def mix(h, p, carry, layer):
        order, n = carry
        return jnp.zeros_like(h), (order.at[n, 1].set(layer), n)

    def ffn(x, carry, place):
        order, n = carry
        return x, (order.at[n, 0].set(place), n + 1)

    _, (order, n) = ssm_hybrid._layers(
        params, cfg, jnp.ones((1, 1, cfg.d_model)), (jnp.zeros((14, 2), jnp.int32), 0),
        {"mamba": mix, "attention": mix}, ffn=ffn)
    assert int(n) == 14
    np.testing.assert_array_equal(order[:, 0], np.arange(14))
    # and each kind's own stack is walked in its own order
    np.testing.assert_array_equal(order[:, 1], [0, 0, 1, 1, 2, 2, 3, 4, 3, 5, 4, 6, 5, 7])


def test_layers_of_experts_alone_need_a_latent(model):
    cfg, _ = model
    with pytest.raises(NotImplementedError, match="work in a latent"):
        ssm_hybrid.period_plan(cfg.with_(moe_latent=0))


@pytest.mark.parametrize("groups", [2, 4])
def test_the_chunked_scan_at_several_groups_is_the_recurrence_with_each_heads_own_group(groups):
    b, t, h, p, n = 2, 50, 8, 16, 8
    x, dt, a, bm, cm, s0 = _scan_inputs(groups, b, t, h, p, n, groups)
    of = jnp.arange(h) // (h // groups)

    def step(s, xs):
        xt, dtt, bt, ct = xs
        s = (jnp.exp(dtt * a)[..., None, None] * s
             + (dtt[..., None] * xt)[..., None] * bt[:, of][:, :, None, :])
        return s, jnp.einsum("bhpn,bhn->bhp", s, ct[:, of])

    s_want, y_want = jax.lax.scan(step, s0, tuple(jnp.moveaxis(z, 1, 0) for z in (x, dt, bm, cm)))
    y, s_end = ssm_scan.ssd_chunked(x, dt, a, bm, cm, s0, 16)
    np.testing.assert_allclose(y, jnp.moveaxis(y_want, 0, 1), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(s_end, s_want, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("live", [[], [1], [0, 2, 3]], ids=["none", "one", "three of four"])
def test_the_state_kernel_at_two_groups_moves_the_listed_slots_with_each_heads_own_group(live):
    slots, layers, h, p, n, g = 4, 2, 8, 64, 16, 2
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    k = ssm_scan.heads_per_row(h, p)
    pool = jax.random.normal(ks[0], (slots, layers) + ssm_scan.state_plane(h, p, n))
    decay = jax.nn.sigmoid(jax.random.normal(ks[1], (slots, h)))
    dtx = jax.random.normal(ks[2], (slots, h, p))
    bm, cm = (jax.random.normal(kk, (slots, g, n)) for kk in ks[3:5])
    mask = jnp.zeros((slots,), bool).at[jnp.asarray(live, jnp.int32)].set(True)
    got, y = ssm_scan.ssm_state_step_auto(pool, 1, ssm_scan.live_slots(mask), decay, dtx, bm, cm)
    of = jnp.arange(h) // (h // g)
    s = ssm_scan.unpack_state(pool[:, 1], k)
    s1 = decay[..., None, None] * s + dtx[..., None] * bm[:, of][:, :, None, :]
    y_want = jnp.einsum("bhpn,bhn->bhp", s1, cm[:, of])
    for i in range(slots):
        if i in live:
            np.testing.assert_allclose(ssm_scan.unpack_state(got[i, 1], k), s1[i], atol=1e-5)
            np.testing.assert_allclose(y[i], y_want[i], atol=1e-4)
        else:
            np.testing.assert_array_equal(got[i], pool[i])
            np.testing.assert_array_equal(y[i], np.zeros((h, p), np.float32))
    np.testing.assert_array_equal(got[:, 0], pool[:, 0])  # the other layer untouched


# -- the expert layer ------------------------------------------------------------


def _layer(cfg, params, place):
    """(the layer's small leaves, the WHOLE expert stacks) at ``place``."""
    whole, small = experts.split_stacks(params["blocks"]["moe"])
    return jax.tree.map(lambda a: a[place], small), whole


FORMS = ("dense", "hit_list", "grouped")


@pytest.mark.parametrize("rows,why", [(6, "six rows: picks land here, elsewhere and both"),
                                      (1, "one row")])
def test_the_two_matrix_kernels_agree_with_the_dense_dispatch(model, rows, why):
    """The hit list and the grouped form over two-matrix relu^2 experts in a
    latent, for a share, against every held expert computing every row."""
    cfg, params = model
    small, stacks = _layer(cfg, params, 1)
    h = jax.random.normal(jax.random.PRNGKey(rows), (rows, 1, cfg.d_model)) * 3
    live = jnp.ones((rows,), jnp.float32)
    dense = small | {k: s[1] for k, s in zip(experts.EXPERT_LEAVES, stacks) if s is not None}
    with jax.default_matmul_precision("highest"):
        outs = {f: experts.moe_ffn(h, dense if f == "dense" else small, cfg, live, f, stacks, 1)
                for f in FORMS}
    for f in FORMS[1:]:
        np.testing.assert_allclose(outs[f][0], outs["dense"][0], atol=1e-5, rtol=1e-5)
        np.testing.assert_array_equal(outs[f][1], outs["dense"][1])
    hit, most, n, held = (int(x) for x in outs["dense"][1])
    assert n == rows and 0 < held <= rows * cfg.n_experts_used and hit <= min(held, 8)
    assert stacks[0] is None and stacks[1].shape[-2:] == (cfg.moe_latent, cfg.moe_d_ff)


def test_the_kernels_alone_are_relu2_of_up_then_down():
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    r, d, f, e = 5, 64, 32, 4
    h = jax.random.normal(ks[0], (r, d))
    up, down = jax.random.normal(ks[1], (2, e, d, f)) * 0.2, jax.random.normal(ks[2], (2, e, f, d)) * 0.2
    gates = jax.nn.relu(jax.random.normal(ks[3], (e, r)))
    init = jax.random.normal(ks[4], (r, d))
    ids, n_hit = moe_experts.hit_list(jnp.asarray([1, 0, 2, 3]), 3)  # experts 0, 2, 3
    with jax.default_matmul_precision("highest"):
        got = moe_experts.moe_hit_experts_auto(h, gates[ids], ids, n_hit, 1, None, up, down, init)
        want = init + sum(gates[i][:, None] * (jnp.square(jax.nn.relu(h @ up[1, i])) @ down[1, i])
                          for i in (0, 2, 3))
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
        sizes = jnp.asarray([2, 0, 3, 0])  # sorted rows: two on expert 0, three on expert 2
        out = moe_experts.moe_grouped_experts_auto(h, gates[0], sizes, 1, None, up, down)
        mine = jnp.asarray([0, 0, 2, 2, 2])
        want = jnp.stack([gates[0, i] * (jnp.square(jax.nn.relu(h[i] @ up[1, mine[i]]))
                                         @ down[1, mine[i]]) for i in range(r)])
        np.testing.assert_allclose(out, want, atol=1e-4, rtol=1e-4)


def test_the_four_shares_and_the_shared_expert_once_are_the_uncut_layer(model):
    """The share test: a layer's 32 experts dealt to four ranks (expert e on
    chip e mod 4 at place e // 4); the four chips' partial sums, the shared
    expert counted once, are the uncut layer, by the program and by the
    reference alike (whose uncut form has chips 1 and all 32 experts)."""
    cfg, _ = model
    whole_conf = dict(CONF, n_routed_experts=32, expert_parallel={"chips": 1, "rank": 0})
    whole_cfg = REF.model_config(whole_conf, SEQ).with_(dtype="float32")
    mp = pytest.MonkeyPatch()
    mp.setattr(weights, "INIT_STD", 0.08)
    try:
        whole = seeded(whole_cfg)
    finally:
        mp.undo()
    place = 2
    small, stacks = _layer(whole_cfg, whole, place)
    hn = jax.random.normal(jax.random.PRNGKey(5), (7, whole_cfg.d_model))
    h = hn[:, None]
    with jax.default_matmul_precision("highest"):
        dense = small | {k: s[place] for k, s in zip(experts.EXPERT_LEAVES, stacks)
                         if s is not None}
        want, _ = experts.moe_ffn(h, dense, whole_cfg)
        ref_routed, ref_shared = REF.share_parts(whole, whole_conf, hn, place)
        routed, shared, ref_sum = 0.0, None, 0.0
        for rank in range(4):
            c = whole_cfg.with_(moe_ep_size=4, moe_ep_rank=rank)
            held = {"w_up_e": stacks[1][place, rank::4], "w_down_e": stacks[2][place, rank::4]}
            part, _ = experts.moe_ffn(h, small | held, c)
            none, _ = experts.moe_ffn(h, small | jax.tree.map(jnp.zeros_like, held), c)
            routed, shared = routed + (part - none), none
            conf = dict(CONF, expert_parallel={"chips": 4, "rank": rank})
            cut = dict(whole, blocks=dict(whole["blocks"], moe=dict(
                whole["blocks"]["moe"], w_up_e=stacks[1][:, rank::4],
                w_down_e=stacks[2][:, rank::4])))
            got_routed, got_shared = REF.share_parts(cut, conf, hn, place)
            ref_sum = ref_sum + got_routed
            np.testing.assert_allclose(got_shared, ref_shared, atol=1e-6)
            # a rank's part by the program is that rank's part by the reference
            np.testing.assert_allclose((part - none)[:, 0], got_routed, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(routed + shared, want, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(ref_sum, ref_routed, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose((routed + shared)[:, 0], ref_routed + ref_shared,
                               atol=1e-4, rtol=1e-4)
    assert float(jnp.abs(want - shared).max()) > 0.05  # the routed part is not nothing


def test_the_live_router_spreads_a_steps_picks_over_the_held_experts(model, prompt):
    """The counters a decode step returns: [n_moe_layers, 4] = experts hit,
    most rows on one, live rows, picks held. One live row of top-6 over 32
    experts holds 0-6 of its picks here."""
    cfg, params = model
    logits, rows = prefill(cfg, params, prompt)
    _, _, stats = decode(cfg, params, into_pool(empty_pools(cfg), rows), entry(logits),
                         len(prompt), 2)
    assert stats.shape == (cfg.n_moe_layers, experts.stats_width(cfg)) == (3, 4)
    hit, most, live, held = np.asarray(stats).T
    assert (live == 1).all() and (hit == held).all() and (held <= 6).all() and held.sum() > 0
    assert (most == (hit > 0)).all()


# -- metadata, pricing, refusals -------------------------------------------------


def test_the_metadata_round_trip_keeps_the_three_kinds_the_latent_and_the_share(model):
    from nats_llm_studio_tpu.models.export import config_metadata

    cfg, _ = model
    md = config_metadata(cfg, "m")
    back = ModelConfig.from_gguf_metadata(md).with_(dtype=cfg.dtype, d_ff=cfg.d_ff)
    assert back == cfg
    assert back.family == "ssm_hybrid" and back.layer_types == tuple(
        REF.layer_kinds(CONF)) and back.mlp_act == "relu2"
    assert (back.n_ssm_layers, back.n_kv_layers, back.n_moe_layers) == (3, 1, 3)
    assert (back.n_experts, back.n_experts_held, back.moe_ep_size, back.moe_ep_rank) == (32, 8, 4, 1)
    assert back.moe_latent == 64 and back.ssm_n_groups == 2 and back.kv_pack == 1
    assert md["nemotron_h_moe.moe_latent_size"] == 64
    assert md["nemotron_h_moe.feed_forward_length"] == [0, 32, 0, 32, 0, 0, 32]
    assert md["nemotron_h_moe.attention.head_count_kv"] == [0, 0, 0, 0, 2, 0, 0]


def test_admission_prices_the_state_pool_and_the_experts_held_at_the_published_widths():
    from nats_llm_studio_tpu.parallel.memory import (
        estimate_device_bytes, kv_pool_block_bytes, state_slot_bytes)

    conf = json.loads((ROOT / "benchmark/configs/nemotron-3-super-120b-a12b.json").read_text())
    cfg = REF.model_config(conf, 4096)
    assert (cfg.n_ssm_layers, cfg.n_kv_layers, cfg.n_moe_layers) == (5, 1, 5)
    assert (cfg.n_experts, cfg.n_experts_held, cfg.n_experts_used) == (512, 128, 22)
    # KV of the one attention layer: 16 tokens x 2 kv heads x 128 x K and V x bf16
    assert kv_pool_block_bytes(cfg, 16) == 2 * 16 * 2 * 128 * 2
    # a slot: a float32 state of 128 x 64 x 128 and 4 bf16 rows of 10,240 channels a layer
    assert state_slot_bytes(cfg) == 5 * (128 * 64 * 128 * 4 + 4 * 10240 * 2) + 4
    est = estimate_device_bytes(cfg, {}, batch=64, seq_len=4096)
    mamba = 4096 * (8192 + 10240) + 4096 * 128 + 5 * 10240 + 3 * 128 + 8192 + 8192 * 4096 + 4096
    attn = 4096 * 4096 + 2 * 4096 * 256 + 4096 * 4096 + 4096
    moe = (4096 * 512 + 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376 + 4096
           + 128 * 2 * 1024 * 2688)
    want = 2 * (5 * mamba + attn + 5 * moe + 4096 + 2 * 4096 * conf["vocab_size"])
    assert abs(est["params"] - want) < 1e-3 * want
    assert abs(want - 9.30e9) < 0.01 * 9.30e9  # the configuration's 9.30 GB of bf16


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


def test_a_mesh_a_verify_bundle_int8_experts_and_a_gguf_of_tensors_are_refused(model):
    from nats_llm_studio_tpu.ops.wquant import quantize_params
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
    with pytest.raises(NotImplementedError, match="no GGUF tensor-name map for state-space"):
        load_params_sharded(None, cfg, build_mesh({"tp": 1}, devices=jax.local_devices()[:1]))
    from nats_llm_studio_tpu.serve.batcher import ContinuousBatcher

    with pytest.raises(ValueError, match="WQUANT=int8 is not implemented for two-matrix"):
        ContinuousBatcher(quantize_params(params), cfg, max_slots=2)
