"""The lightning / block-sparse family (``models/sala.py``, ``ops/lightning.py``,
the picked walk of ``ops/paged_attention.py``) against its plain reference
(``benchmark/references/sala.py``: the recurrence a token at a time, the
sparse layer as its equations say a query) on seeded weights, at toy size on
the CPU: logits, not tokens. The served side is driven the way the batcher
drives it: ``models.llama.forward`` prefill (whole, in chunks, or as a padded
group) into row caches that carry the rows' state and pooled keys, written
into the pool (KV by table, the rest by slot), then ``forward_decode_paged``
steps (the state kernel and the picked walk in interpreter mode). The toy is
a list with no period (sparse, lightning x3, sparse x2); a query past 96 keys
picks 5 of up to 16 blocks of 16. The q / k norm gains are drawn loud (the
harness's are ones, which leave every score with a spread of 1): peaked
scores, so that what is picked decides the logits. Faults put in on purpose
are in ``tests/test_sala_faults.py``, the live batcher in
``tests/test_sala_served.py``: three files, so that three workers share them."""

import json
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run
from benchmark.lib import correct, weights
from nats_llm_studio_tpu.models import llama, sala
from nats_llm_studio_tpu.models.config import ModelConfig
from nats_llm_studio_tpu.ops import lightning, ssm_scan
from nats_llm_studio_tpu.ops import paged_attention as pa
from nats_llm_studio_tpu.ops.kvcache import (
    WithState, kv_pool_write_row, kv_pool_zeros, state_write_row)

ROOT = Path(__file__).resolve().parents[1]
CONF = json.loads((ROOT / "benchmark/tests/rehearsal/configs/tiny-sala.json").read_text())
REF = run.load_module(ROOT / "benchmark/references/sala.py")

T, SEQ, SLOTS = 16, 256, 3  # pool block tokens = the toy's sparse block; a slot's table spans SEQ
DENSE = CONF["sparse_config"]["dense_len"]
SLOT = 1
TABLE = [3, 5, 2, 7, 1, 4, 6, 8, 9, 12, 11, 10, 13, 14, 15, 16]
# float32 through six toy layers: the sound path agrees to ~1e-4 on
# log-probabilities of logits with a spread of 5. The limits sit two orders
# above it and every fault (tests/test_sala_faults.py) far above them
TOY_FIRST = {"median_tol": 0.01, "token_tol": 0.03}
TOY_DECODED = {"median_tol": 0.01, "token_tol": 0.03, "gap_tol": 0.03}


def seeded(cfg, seed=4321):
    from nats_llm_studio_tpu.parallel.mesh import build_mesh

    mesh = build_mesh({"tp": 1}, devices=jax.local_devices()[:1])
    family = types.SimpleNamespace(param_shapes=REF.param_shapes, weight_gains=REF.weight_gains)
    params = weights.make_seeded_params(seed, family)(None, cfg, mesh)
    key = jax.random.PRNGKey(seed)
    for stack in ("attn", "linear"):      # loud head norms: scores with a spread near 4
        for name in ("q_norm", "k_norm"):
            key, k = jax.random.split(key)
            leaf = params["blocks"][stack][name]
            params["blocks"][stack][name] = 2.0 + 0.5 * jax.random.normal(k, leaf.shape)
    return params


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


def entry(logits) -> dict:
    lp = np.asarray(jax.nn.log_softmax(jnp.asarray(logits, jnp.float32)))

    def one(i):
        return {"token": chr(int(i)), "bytes": [int(i)], "logprob": float(lp[i])}

    return dict(one(int(np.argmax(lp))),
                top_logprobs=[one(i) for i in np.argsort(-lp)[:correct.TOP_K]])


def empty_pools(cfg):
    shape = (1 + len(TABLE), cfg.n_kv_layers, cfg.n_kv_heads, T, cfg.head_dim)
    return tuple(WithState(kv_pool_zeros(shape, jnp.dtype(cfg.dtype)), st, ax)
                 for st, ax in sala.make_state(cfg, SLOTS))


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
    """A prefilled row's KV into the table's blocks and its state and pooled
    keys into the slot's row: what ``serve/programs.py pool_write`` does."""
    bids = jnp.asarray(TABLE, jnp.int32)
    return tuple(WithState(kv_pool_write_row(p.kv, r.kv, bids), state_write_row(p, r.st, slot),
                           p.axes) for p, r in zip(pools, rows))


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


def serve(cfg, params, prompt, n, pools=None, **how):
    logits, rows = prefill(cfg, params, prompt, **how)
    pools = into_pool(pools or empty_pools(cfg), rows)
    return decode(cfg, params, pools, entry(logits), len(prompt), n - 1)


def check(params, prompt, entries) -> dict:
    toks = correct.served_tokens(entries)
    ref = REF.tail_logprobs(params, CONF, list(prompt) + toks[:-1], len(toks))
    return correct.compare_probes([(ref, entries)], TOY_FIRST, TOY_DECODED)


CASES = {
    "a prompt under the dense length, decoded under it": (40, 12, None),
    "a prompt that crosses the dense length inside its second chunk": (120, 6, (64, 56)),
    "decode that crosses the dense length": (DENSE - 6, 14, None),
    "decode through three pooled-key boundaries and a block boundary": (138, 12, None),
    "three chunks that start off the stride, the last one padded": (150, 6, (37, 100, 13)),
}


@pytest.mark.parametrize("case", list(CASES))
def test_prefill_then_paged_decode_steps_agree_with_the_reference(model, case):
    """The chunked rule and the state kernel, rotary q and k under head norms,
    the output norm over all heads and the gates; plain attention under the
    dense length, the masked prefill and the picked walk past it, by the
    query's own length; the pooled keys written by the chunk that completes
    them and by the decode step that does; muP scalings."""
    cfg, params = model
    n, steps, chunks = CASES[case]
    prompt = tokens(1, n)
    entries, _ = serve(cfg, params, prompt, steps + 1, chunks=chunks,
                       pad=24 if chunks and sum(chunks) == 150 else 0)
    out = check(params, prompt, entries)
    assert out["ok"] and out["first_ok"] and out["decoded"]["ok"], out
    assert out["decoded"]["positions"] == steps
    assert out["max_abs_diff"] < 2e-3 and out["decoded"]["max_abs_diff"] < 2e-3, out


def test_a_group_of_rows_of_different_lengths_is_each_rows_own_prefill(model):
    """A chunked group admit: three rows share two chunk launches of 64, one
    ends in the first chunk (its second chunk is all padding: no real
    position, told by a negative ``logit_positions``), one ends inside the
    second, one fills it. Each row's logits, state, ``seen`` and pooled keys
    (up to its own length) are those of the row prefilled alone."""
    cfg, params = model
    lens, c = [50, 101, 128], 64
    prompts = [tokens(10 + i, n) for i, n in enumerate(lens)]
    k, v = llama.make_cache(cfg, 3, SEQ)
    ends = {}
    for j in range(2):
        toks = [(p[j * c:(j + 1) * c] + [0] * c)[:c] for p in prompts]
        last = jnp.asarray([min(n - j * c, c) - 1 if n > j * c else -1 for n in lens], jnp.int32)
        logits, k, v = llama.forward(
            params, cfg, jnp.asarray(toks, jnp.int32), k, v, jnp.full((3,), j * c, jnp.int32),
            logit_positions=last, fresh_prefill=j == 0, uniform_start=True)
        for i, n in enumerate(lens):
            if j * c < n <= (j + 1) * c:
                ends[i] = logits[i, -1]
    stride, kernel = cfg.sparse_stride, cfg.sparse_kernel
    for i, (p, n) in enumerate(zip(prompts, lens)):
        alone, (k1, v1) = prefill(cfg, params, p)
        np.testing.assert_allclose(ends[i], alone, atol=2e-4)
        np.testing.assert_allclose(v.st[0][i], v1.st[0][0], atol=2e-4)
        assert int(k.st[1][i]) == n == int(k1.st[1][0])
        complete = (n - kernel) // stride + 1
        np.testing.assert_allclose(k.st[0][:, i, :, :complete], k1.st[0][:, 0, :, :complete],
                                   atol=1e-5)


def test_a_slot_taken_again_reads_nothing_the_last_request_left(model):
    """A slot served a long request and then a short one: the second row's
    state starts from zeros and its queries read no pooled key past its own
    length, whatever the first left in the slot's pooled keys and the pool's
    blocks (junk is written there on purpose)."""
    cfg, params = model
    _, pools = serve(cfg, params, tokens(3, 200), 4)
    junk = tuple(WithState(p.kv, tuple(jnp.where(jnp.issubdtype(a.dtype, jnp.floating),
                                                 a + 7.0, a) for a in p.st), p.axes)
                 for p in pools)
    prompt = tokens(4, 100)
    entries, _ = serve(cfg, params, prompt, 10, pools=junk)
    out = check(params, prompt, entries)
    assert out["ok"] and out["max_abs_diff"] < 2e-3 and out["decoded"]["max_abs_diff"] < 2e-3, out


def test_a_replayed_position_reads_the_state_and_rewrites_what_it_wrote(model):
    """The batcher replays a prompt's last position through the decode program
    (requests with logprobs or a grammar): the lightning state is read and not
    advanced (``seen``), the sparse layer writes the same key again, and the
    logits are the prefill's."""
    cfg, params = model
    prompt = tokens(5, 112)   # 112 = 8 + 4 x 26: the replayed key completes a pooled key
    first, rows = prefill(cfg, params, prompt)
    pools = into_pool(empty_pools(cfg), rows)
    tbl = jnp.zeros((SLOTS, len(TABLE)), jnp.int32).at[SLOT].set(jnp.asarray(TABLE))
    tok = jnp.zeros((SLOTS, 1), jnp.int32).at[SLOT, 0].set(prompt[-1])
    pos = jnp.zeros((SLOTS,), jnp.int32).at[SLOT].set(len(prompt) - 1)
    logits, kp, vp = llama.forward_decode_paged(params, cfg, tok, *pools, tbl, pos)
    np.testing.assert_allclose(logits[SLOT, -1], first, atol=2e-4)
    np.testing.assert_array_equal(vp.st[0], pools[1].st[0])
    assert int(kp.st[1][SLOT]) == len(prompt)


def test_the_fp8_control_is_far_from_the_reference(model):
    """``--control fp8`` rounds the products' inputs, the cached keys and
    values and the pooled keys, and leaves the recurrent state in float32."""
    _, params = model
    toks = tokens(1, 120) + tokens(2, 16)
    ref = REF.tail_logprobs(params, CONF, toks, 16)
    low = REF.tail_logprobs(params, CONF, toks, 16, lower="fp8")
    best = np.argsort(-ref, axis=-1)[:, :correct.TOP_K]
    assert np.median(np.take_along_axis(np.abs(low - ref), best, axis=-1)) > 0.3
    with pytest.raises(ValueError):
        REF.tail_logprobs(params, CONF, toks, 16, lower="int4")


@pytest.mark.parametrize("rate", [1e-5, 1.0, "a spread"], ids=str)
@pytest.mark.parametrize("chunk", [128, 7])
def test_the_chunked_rule_is_the_token_by_token_rule(rate, chunk):
    """300 positions in chunks of 128 (two whole, one padded) and of 7, from a
    state that is not zero, rows that end at 300, at 130 (inside a chunk) and
    at 0 (a row with no real position keeps its state bit for bit), at the
    slowest decay the table holds, the fastest, and a spread of both."""
    b, t, h, dk, dv = 3, 300, 4, 16, 32
    ks = jax.random.split(jax.random.PRNGKey(chunk), 5)
    q, k = (jax.random.normal(kk, (b, t, h, dk)) for kk in ks[:2])
    v, s0 = jax.random.normal(ks[2], (b, t, h, dv)), jax.random.normal(ks[3], (b, h, dk, dv))
    a = jnp.asarray([1e-5, 1.0, 0.3, 0.01]) if rate == "a spread" else jnp.full((h,), rate)
    valid = jnp.asarray([300, 130, 0], jnp.int32)
    real = jnp.arange(t)[None, :] < valid[:, None]
    with jax.default_matmul_precision("highest"):
        o0, s_seq = lightning.lightning_recurrent(q, k, v, a, real, s0)
        o1, s_chk = jax.jit(lightning.lightning_chunked, static_argnums=6)(
            q, k, v, a, valid, s0, chunk)
    scale = float(jnp.abs(o0).max())
    np.testing.assert_allclose(jnp.where(real[..., None, None], o1, 0.0),
                               jnp.where(real[..., None, None], o0, 0.0), atol=2e-5 * scale)
    np.testing.assert_allclose(s_chk, s_seq, atol=2e-5 * float(jnp.abs(s_seq).max()))
    np.testing.assert_array_equal(s_chk[2], s0[2])


_STATE_STEP = jax.jit(lightning.lightning_step_auto)
LIVE_SETS = {"none": [], "slot 0 only": [0], "the last slot only": [9],
             "every other slot": [0, 2, 4, 6, 8], "all": list(range(10))}


@pytest.mark.parametrize("name", list(LIVE_SETS))
def test_the_state_kernel_is_the_xla_step_on_the_listed_slots_and_no_other(name):
    """The list is data: a listed slot is the XLA step, a slot that is not
    listed keeps its state bit for bit and gives zeros, whatever its row of the
    operands holds (NaN here). The first listed row replays a position (decay
    1, v 0): it reads S^T q and keeps its state. Every live set runs the one
    compiled program, and only layer 1 moves."""
    slots, layers, h, dk, dv = 10, 2, 4, 16, 128
    live = LIVE_SETS[name]
    dead = [i for i in range(slots) if i not in live]
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    pool = jax.random.normal(ks[0], (slots, layers, h, dk, dv))
    decay = jax.random.uniform(ks[1], (slots, h))
    q, k = (jax.random.normal(kk, (slots, h, dk)) for kk in ks[2:4])
    v = jax.random.normal(ks[4], (slots, h, dv))
    if live:
        decay, v = decay.at[live[0]].set(1.0), v.at[live[0]].set(0.0)
    every = ssm_scan.live_slots(jnp.ones((slots,), bool))
    want, y_want = lightning.lightning_step_xla(pool, 1, every, decay, q, k, v)
    nan = jnp.asarray(dead, jnp.int32)
    decay, q, k, v = (z.at[nan].set(jnp.nan) for z in (decay, q, k, v))
    listed = ssm_scan.live_slots(jnp.zeros((slots,), bool).at[jnp.asarray(live, jnp.int32)].set(True))
    got, y = _STATE_STEP(pool, 1, listed, decay, q, k, v)
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
    np.testing.assert_array_equal(got[:, 0], pool[:, 0])  # the other layer untouched


@pytest.mark.parametrize("t,width", [(16, 12), (64, 5)], ids=["blocks of 16", "blocks of 64"])
def test_the_picked_walk_is_attention_over_the_picked_blocks(t, width):
    """Picks that repeat no block, a table a kv head, in no order: a head that
    walks all its entries and ends on a full block, one that walks some and
    ends inside a block, one that walks one entry of one key; both layers."""
    b, hq, hkv, d, layers, nb = 3, 8, 2, 128, 2, 40
    ks = jax.random.split(jax.random.PRNGKey(t), 4)
    q = jax.random.normal(ks[0], (b, 1, hq, d))
    kp, vp = (jax.random.normal(kk, (nb, layers, hkv, t, d)) for kk in ks[1:3])
    ent = jnp.stack([jax.random.permutation(kk, jnp.arange(1, nb))[:hkv * width].reshape(hkv, width)
                     for kk in jax.random.split(ks[3], b)])
    cnt = jnp.asarray([[width, width], [width // 2, width - 1], [1, 1]], jnp.int32)
    last = jnp.asarray([t, 3, 1], jnp.int32)
    for layer in range(layers):
        got = pa.paged_decode_attention_picked_auto(q, kp, vp, ent, cnt, last, jnp.int32(layer), 0.1)
        want = pa.paged_decode_attention_picked_xla(q, kp, vp, ent, cnt, last, layer, 0.1)
        np.testing.assert_allclose(got, want, atol=2e-6)


def test_the_published_stack_is_a_list_of_nine_runs_and_loads_by_its_header():
    """The published 32-entry ``mixer_types`` through the program's own
    initialiser (shapes only): 24 lightning and 8 sparse layers, one period,
    and the decay table at the published indices. The 8-layer cut's GGUF
    header gives the same config back, the cut's place in the stack with it."""
    from nats_llm_studio_tpu.models.export import config_metadata
    from nats_llm_studio_tpu.models.ssm_hybrid import period_plan

    conf = json.loads((ROOT / "benchmark/configs/minicpm-sala.json").read_text())
    whole = dict(conf, num_hidden_layers=32, mixer_types=conf["published"]["mixer_types"],
                 stage_first_layer=0)
    cfg = REF.model_config(whole, 28672)
    assert (cfg.family, cfg.n_lin_layers, cfg.n_kv_layers) == ("sala", 24, 8)
    assert period_plan(cfg) == (1, cfg.layer_types)
    shapes = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) == 9_477_206_784
    assert shapes["blocks"]["linear"]["decay"].shape == (24, 32)
    cut = REF.model_config(conf, 28672)
    assert cut.layer_types == ("sparse",) + ("lightning",) * 6 + ("sparse",)
    assert (cut.stage_first_layer, cut.stage_depth) == (9, 32)
    back = ModelConfig.from_gguf_metadata(config_metadata(cut, "cut"))
    assert back.with_(dtype=cut.dtype) == cut
    # the table at the cut's published indices 10..15: the rate of head 0 falls
    # with the layer, and every rate lies in (0, 1)
    table = lightning.rates(lightning.decay_table([10, 15, 31], 32, 32))
    assert float(table[0, 0]) > float(table[1, 0]) > float(table[2, 0]) > 0
    assert float(table.max()) < 1 and abs(float(table[0, 0]) - 2 ** -0.25 * (1 - 10 / 31 + 1e-5)) < 1e-6
    with pytest.raises(NotImplementedError):
        md = config_metadata(cut, "cut")
        ModelConfig.from_gguf_metadata(md | {"minicpm_sala.attention.use_rope": True})


def test_the_sparse_counters_count_what_a_row_walks():
    """``BatcherStats.record_sparse``: a row under the dense length walks all
    it sees; past it, 63 whole blocks and its frontier block's keys, a layer."""
    from nats_llm_studio_tpu.serve.batcher import BatcherStats

    conf = json.loads((ROOT / "benchmark/configs/minicpm-sala.json").read_text())
    cfg = REF.model_config(conf, 28672)
    st = BatcherStats()
    out = st.record_sparse([8190, 20000], 3, cfg)
    live = (8191 + 8192 + 8193) + (20001 + 20002 + 20003)
    picked = (8191 + 8192) + (63 * 64 + 8193 % 64 or 64) + sum(
        63 * 64 + (n - 1) % 64 + 1 for n in (20001, 20002, 20003))
    assert out == {"sparse_tokens_live": 2 * live, "sparse_tokens_picked": 2 * picked,
                   "sparse_rows_dense": 2 * 2}
    assert st.sparse_counters() == {"tokens_live": 2 * live, "tokens_picked": 2 * picked,
                                    "rows_dense": 4}
