"""Sampling invariance tests for the constrained-decoding extension.

The ``mask`` parameter added to ``_pick`` / ``_log_weights`` must be a
bitwise no-op when absent: ``_pick_ref`` / ``_log_weights_ref`` below are
verbatim copies of the pre-extension implementations, and every
unconstrained path is asserted bit-identical against them — greedy,
seeded temperature, top-k, top-p, and the per-row ``sample_rows`` stream.
With a mask, selection must stay inside the allowed set and greedy must
equal argmax over the allowed logits; at the batcher level, masked greedy
through ``ContinuousBatcher`` must reproduce a from-scratch reference
loop token for token on both KV layouts."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nats_llm_studio_tpu.engine.generator import SamplingParams
from nats_llm_studio_tpu.engine.sampling import (
    FUNNEL,
    _log_weights,
    _pick,
    gumbel_at,
    require_partitionable_threefry,
    row_class,
    sample_rows,
    spec_accept_rows,
    top_candidates,
)
from nats_llm_studio_tpu.models.config import ModelConfig
from nats_llm_studio_tpu.models.llama import (
    ensure_lm_head,
    forward,
    init_params,
    make_cache,
)
from nats_llm_studio_tpu.serve.batcher import ContinuousBatcher

from conftest import async_test

CANDIDATES = 64
_NEG_INF = jnp.float32(-jnp.inf)


# -- verbatim pre-extension implementations (the invariance baseline) -------


def _pick_ref(logits, gumbel, temperature, top_k, top_p) -> jax.Array:
    """Shared sort-free selection. gumbel: [B, V] standard Gumbel noise."""
    b, v = logits.shape
    temperature = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (b,))
    top_k = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), (b,))
    top_p = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), (b,))
    safe_t = jnp.maximum(temperature, 1e-6)[:, None]

    greedy = jnp.argmax(logits, axis=-1)
    full_pick = jnp.argmax(logits / safe_t + gumbel, axis=-1)

    c = min(CANDIDATES, v)
    cand, cand_idx = jax.lax.top_k(logits, c)
    ranks = jnp.arange(c)[None, :]
    k_eff = jnp.where(top_k <= 0, c, jnp.minimum(top_k, c))[:, None]
    keep = ranks < k_eff
    probs = jax.nn.softmax(cand / safe_t, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep &= (cum - probs) < top_p[:, None]
    g_cand = jnp.take_along_axis(gumbel, cand_idx, axis=-1)
    masked = jnp.where(keep, cand / safe_t, _NEG_INF)
    drawn = jnp.argmax(masked + g_cand, axis=-1)
    cand_pick = jnp.take_along_axis(cand_idx, drawn[:, None], axis=-1)[:, 0]

    restricted = ((top_k > 0) & (top_k < v)) | (top_p < 1.0)
    pick = jnp.where(restricted, cand_pick, full_pick)
    return jnp.where(temperature <= 0.0, greedy, pick).astype(jnp.int32)


def _log_weights_ref(logits, temperature, top_k, top_p) -> jax.Array:
    b, v = logits.shape
    temperature = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (b,))
    top_k = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), (b,))
    top_p = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), (b,))
    safe_t = jnp.maximum(temperature, 1e-6)[:, None]

    c = min(CANDIDATES, v)
    cand, cand_idx = jax.lax.top_k(logits, c)
    ranks = jnp.arange(c)[None, :]
    k_eff = jnp.where(top_k <= 0, c, jnp.minimum(top_k, c))[:, None]
    keep = ranks < k_eff
    probs = jax.nn.softmax(cand / safe_t, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep &= (cum - probs) < top_p[:, None]
    rows = jnp.arange(b)[:, None]
    masked = jnp.full((b, v), _NEG_INF).at[rows, cand_idx].set(
        jnp.where(keep, cand / safe_t, _NEG_INF)
    )
    restricted = (((top_k > 0) & (top_k < v)) | (top_p < 1.0))[:, None]
    return jnp.where(restricted, masked, logits / safe_t)


SETTINGS = [
    (0.0, 0, 1.0),   # greedy
    (0.8, 0, 1.0),   # unrestricted temperature
    (1.3, 5, 1.0),   # top-k
    (0.7, 0, 0.9),   # top-p
    (1.0, 8, 0.75),  # both
]


def _logits_gumbel(b=6, v=200, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    logits = jax.random.normal(k1, (b, v), jnp.float32) * 3.0
    gumbel = jax.random.gumbel(k2, (b, v), jnp.float32)
    return logits, gumbel


@pytest.mark.parametrize("temp,tk,tp", SETTINGS)
def test_pick_no_mask_bit_identical(temp, tk, tp):
    for seed in range(3):
        logits, gumbel = _logits_gumbel(seed=seed)
        got = _pick(logits, gumbel, temp, tk, tp)
        want = _pick_ref(logits, gumbel, temp, tk, tp)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("temp,tk,tp", SETTINGS)
def test_log_weights_no_mask_bit_identical(temp, tk, tp):
    logits, _ = _logits_gumbel(seed=11)
    got = np.asarray(_log_weights(logits, temp, tk, tp))
    want = np.asarray(_log_weights_ref(logits, temp, tk, tp))
    # -inf == -inf must also compare equal — array_equal handles it
    np.testing.assert_array_equal(got, want)


def test_sample_rows_no_mask_bit_identical():
    logits, _ = _logits_gumbel(seed=5)
    b, v = logits.shape
    seeds = jnp.arange(100, 100 + b, dtype=jnp.int32)
    steps = jnp.arange(b, dtype=jnp.int32) * 3

    def row_gumbel(seed, step):
        k = jax.random.fold_in(jax.random.PRNGKey(seed), step)
        return jax.random.gumbel(k, (v,), jnp.float32)

    gumbel = jax.vmap(row_gumbel)(seeds, steps)
    for temp, tk, tp in SETTINGS:
        got = sample_rows(logits, seeds, steps, temp, tk, tp)
        want = _pick_ref(logits, gumbel, temp, tk, tp)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("temp,tk,tp", SETTINGS)
def test_masked_pick_stays_in_allowed_set(temp, tk, tp):
    logits, gumbel = _logits_gumbel(seed=7)
    b, v = logits.shape
    mask = np.zeros((b, v), dtype=bool)
    rng = np.random.default_rng(3)
    for i in range(b):
        mask[i, rng.choice(v, size=17, replace=False)] = True
    picked = np.asarray(_pick(logits, gumbel, temp, tk, tp, mask=jnp.asarray(mask)))
    for i in range(b):
        assert mask[i, picked[i]], (i, picked[i])
    if temp <= 0.0:
        # masked greedy == argmax over the allowed logits
        want = np.where(mask, np.asarray(logits), -np.inf).argmax(axis=-1)
        np.testing.assert_array_equal(picked, want)


def test_masked_log_weights_bans_tokens():
    logits, _ = _logits_gumbel(seed=9)
    b, v = logits.shape
    mask = np.ones((b, v), dtype=bool)
    mask[:, ::2] = False  # ban every even token id
    w = np.asarray(_log_weights(logits, 0.9, 0, 1.0, mask=jnp.asarray(mask)))
    assert np.all(w[:, ::2] == -np.inf)
    assert np.all(np.isfinite(w[:, 1::2]))
    # all-True mask is the identity
    w_id = np.asarray(
        _log_weights(logits, 0.9, 0, 1.0, mask=jnp.ones((b, v), dtype=bool))
    )
    np.testing.assert_array_equal(w_id, np.asarray(_log_weights_ref(logits, 0.9, 0, 1.0)))


def test_masked_spec_accept_greedy_stays_in_allowed_set():
    b, t, v = 3, 4, 120
    logits = jax.random.normal(jax.random.PRNGKey(21), (b, t, v), jnp.float32)
    mask = np.zeros((b, t, v), dtype=bool)
    allowed = np.arange(10, 40)
    mask[:, :, allowed] = True
    masked_greedy = np.where(mask, np.asarray(logits), -np.inf).argmax(axis=-1)
    drafts = jnp.asarray(masked_greedy[:, : t - 1], jnp.int32)
    toks, n_emit = spec_accept_rows(
        logits, drafts, jnp.full((b,), t - 1, jnp.int32),
        jnp.zeros((b,), jnp.int32), jnp.zeros((b,), jnp.int32),
        temperature=0.0, mask=jnp.asarray(mask),
    )
    toks, n_emit = np.asarray(toks), np.asarray(n_emit)
    # drafts equal to the masked argmax: all accepted + masked-greedy bonus
    np.testing.assert_array_equal(n_emit, np.full((b,), t))
    np.testing.assert_array_equal(toks, masked_greedy)


# -- batcher-level: masked greedy vs a from-scratch reference loop ----------


class AllowSet:
    """Minimal token-DFA fake: every state allows the same id set."""

    def __init__(self, allowed, vocab):
        self.allowed = sorted(allowed)
        self.vocab = vocab
        self.start = 0

    def mask(self, state):
        m = np.zeros(self.vocab, dtype=bool)
        m[self.allowed] = True
        return m

    def advance(self, state, tid):
        return state + 1 if tid in self.allowed else None

    def live(self, state):
        return True

    def accepting(self, state):
        return True


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig.tiny(n_layers=2, max_seq_len=64)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def masked_greedy_reference(cfg, params, prompt, n, allowed):
    """Full re-forward each step: no KV cache, no batcher — the slowest,
    most obviously-correct masked greedy decode."""
    params = ensure_lm_head(params)
    allow = np.zeros(cfg.vocab_size, dtype=bool)
    allow[list(allowed)] = True
    toks = list(prompt)
    out = []
    for _ in range(n):
        k, v = make_cache(cfg, 1, seq_len=64)
        logits, _, _ = forward(
            params, cfg, jnp.asarray([toks], jnp.int32), k, v,
            jnp.zeros((1,), jnp.int32),
        )
        row = np.asarray(logits[0, len(toks) - 1], np.float32)
        t = int(np.where(allow, row, -np.inf).argmax())
        out.append(t)
        toks.append(t)
    return out


@pytest.mark.parametrize("paged", [True, False])
@async_test
async def test_batcher_masked_greedy_matches_reference(model, paged):
    cfg, params = model
    allowed = list(range(10, 30))
    prompts = [[1, 2, 3], [9, 8, 7, 6]]
    want = [masked_greedy_reference(cfg, params, p, 6, allowed) for p in prompts]

    b = ContinuousBatcher(
        params, cfg, max_slots=4, max_seq_len=64, buckets=[8, 64],
        paged=paged, spec_decode_k=(0 if paged else 3),
    )
    try:
        async def run(p):
            sp = SamplingParams(temperature=0.0, max_tokens=6)
            dfa = AllowSet(allowed, cfg.vocab_size)
            return [t async for t in b.submit(p, sp, constrain=dfa)]

        got = await asyncio.gather(*[run(p) for p in prompts])
        assert list(got) == want
    finally:
        b.stop()


@async_test
async def test_batcher_unconstrained_rides_along_unchanged(model):
    """An unconstrained greedy request decoding alongside a constrained one
    (i.e. through the masked ext program with an all-True row) must produce
    exactly what it produces alone through the plain program."""
    cfg, params = model
    prompt = [5, 4, 3, 2]
    b = ContinuousBatcher(params, cfg, max_slots=4, max_seq_len=64, buckets=[8, 64])
    try:
        sp = SamplingParams(temperature=0.0, max_tokens=6)
        alone = [t async for t in b.submit(prompt, sp)]

        dfa = AllowSet(list(range(10, 30)), cfg.vocab_size)

        async def constrained():
            return [t async for t in b.submit([1, 2], sp, constrain=dfa)]

        async def plain():
            return [t async for t in b.submit(prompt, sp)]

        rc, rn = await asyncio.gather(constrained(), plain())
        assert rn == alone
        assert all(t in dfa.allowed for t in rc)
    finally:
        b.stop()


@async_test
async def test_batcher_logprobs_greedy_top_entry_is_chosen_token(model):
    cfg, params = model
    b = ContinuousBatcher(params, cfg, max_slots=4, max_seq_len=64, buckets=[8, 64])
    try:
        sp = SamplingParams(temperature=0.0, max_tokens=5)
        plain = [t async for t in b.submit([3, 1, 4], sp)]
        out = []
        async for batch in b.submit_batched(
            [3, 1, 4], sp, want_logprobs=True, top_logprobs=4
        ):
            out.extend(batch)
        toks = [t for t, _, _, _ in out]
        assert toks == plain  # logprobs request decodes the same tokens
        for tok, lp, top_ids, top_lps in out:
            assert lp <= 0.0
            assert len(top_ids) >= 4 and len(top_lps) >= 4
            # greedy: the chosen token is the most likely one
            assert top_ids[0] == tok
            assert abs(top_lps[0] - lp) < 1e-5
            assert all(a >= b2 for a, b2 in zip(top_lps, top_lps[1:]))
    finally:
        b.stop()


# -- a row's cost follows what it asks for: same tokens, less work ----------
#
# ``sample_rows`` draws noise at a restricted row's candidates only, finds
# the candidates through a funnel, and runs each branch only while a row of
# its class is in the batch. Every token must stay ``_pick_ref``'s over the
# row's WHOLE draw.

@pytest.mark.parametrize("v", [49_155, 100_352, 131_072, 20_001])
def test_candidates_noise_is_the_whole_draws_at_every_id(v):
    """ALL ids, not a sample: a JAX whose draw lays its bits out otherwise
    fails here and not in a served token."""
    key = jax.random.fold_in(jax.random.PRNGKey(1234), 56)
    want = jax.random.gumbel(key, (v,), jnp.float32)
    got = jax.jit(gumbel_at)(key, jnp.arange(v, dtype=jnp.int32))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and in the candidates' own shape, ids in any order, under vmap
    ids = jax.random.randint(jax.random.PRNGKey(v), (3, CANDIDATES), 0, v)
    keys = jax.vmap(lambda s: jax.random.fold_in(jax.random.PRNGKey(s), 9))(jnp.arange(3))
    rows = jax.vmap(lambda k: jax.random.gumbel(k, (v,), jnp.float32))(keys)
    np.testing.assert_array_equal(
        np.asarray(jax.vmap(gumbel_at)(keys, ids)),
        np.asarray(jnp.take_along_axis(rows, ids, axis=-1)))


def test_the_sampler_refuses_another_layout_of_the_draw():
    require_partitionable_threefry()
    jax.config.update("jax_threefry_partitionable", False)
    try:
        with pytest.raises(RuntimeError, match="jax_threefry_partitionable"):
            require_partitionable_threefry()
    finally:
        jax.config.update("jax_threefry_partitionable", True)


def _funnel_rows(case: str, v: int) -> jax.Array:
    GROUP = FUNNEL[0]
    rng = np.random.default_rng(len(case) * 1000 + v)
    x = rng.normal(size=(5, v)).astype(np.float32) * 3.0
    if case == "runs_of_equals":
        # few distinct values, so equals run across every group's edge (of
        # both stages) and the 64th rank falls inside a run; one row all equal
        x = np.round(x)
        x[1, GROUP - 3:GROUP + 3] = 50.0  # the top run straddles groups 0 and 1
        x[2, :] = 0.25
        # 70 equal maxima, one a group: the 64 groups kept are the LOWER ones
        x[3, (np.arange(70) * GROUP + 5) % v] = 40.0
        # and 70 in the narrower groups of one wide group's neighbourhood
        x[4, (np.arange(70) * FUNNEL[1] + 3) % v] = 40.0
    elif case == "masked_to_few":
        keep = np.zeros((5, v), bool)
        for i, n in enumerate((1, 17, 40, 63, 64)):
            keep[i, rng.choice(v, size=n, replace=False)] = True
        keep[0, :] = False
        keep[0, v - 1] = True  # the one finite logit is the last id
        x = np.where(keep, x, -np.inf).astype(np.float32)
    return jnp.asarray(x)


@pytest.mark.parametrize("case,v", [
    ("random", 100_352), ("random", 49_155), ("random", 20_001),
    ("runs_of_equals", 100_352), ("runs_of_equals", 20_001),
    ("masked_to_few", 49_155), ("masked_to_few", 9_000),
    ("random", 8_192), ("runs_of_equals", 8_192), ("masked_to_few", 200),
    ("random", 1_024), ("runs_of_equals", 1_031), ("masked_to_few", 1_500), ("random", 63),
])
def test_the_funnel_is_top_k_value_for_value_and_id_for_id(case, v):
    logits = _funnel_rows(case, v)
    want_v, want_i = jax.lax.top_k(logits, min(CANDIDATES, v))
    got_v, got_i = jax.jit(top_candidates)(logits)
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))


_CLASS_ROWS = {  # rows to stand beside the one under test
    "alone": [],
    "beside_greedy": [(0.0, 0, 1.0), (0.0, 7, 0.5)],
    "beside_restricted": [(0.8, 40, 1.0), (1.1, 0, 0.6)],
    "beside_unrestricted": [(0.9, 0, 1.0)],
    "mixed": [(0.0, 0, 1.0), (0.8, 40, 1.0), (0.9, 0, 1.0), (1.0, 8, 0.75)],
}


@pytest.mark.parametrize("temp,tk,tp", SETTINGS)
def test_a_rows_token_is_its_own_whichever_branches_the_batch_ran(temp, tk, tp):
    """The row alone, beside greedy rows only, beside restricted rows only,
    beside an unrestricted row and in a mixed batch: every combination of
    the two conditionals, at a vocabulary wide enough for the funnel (and
    not a multiple of 128). The token is ``_pick_ref``'s over the row's
    whole draw each time."""
    v = 9_001
    logits_all = jax.random.normal(jax.random.PRNGKey(3), (8, v), jnp.float32) * 3.0
    draw = jax.jit(sample_rows)
    for seed in (11, 12, 13):
        row_logits = logits_all[seed % 8]
        key = jax.random.fold_in(jax.random.PRNGKey(seed), 4)
        gumbel = jax.random.gumbel(key, (v,), jnp.float32)
        want = int(_pick_ref(row_logits[None], gumbel[None], temp, tk, tp)[0])
        for name, others in _CLASS_ROWS.items():
            rows = [(temp, tk, tp)] + others
            n = len(rows)
            got = draw(
                jnp.concatenate([row_logits[None], logits_all[1:n]]),
                jnp.asarray([seed] + [90 + i for i in range(n - 1)], jnp.int32),
                jnp.asarray([4] + [i for i in range(n - 1)], jnp.int32),
                jnp.asarray([r[0] for r in rows], jnp.float32),
                jnp.asarray([r[1] for r in rows], jnp.int32),
                jnp.asarray([r[2] for r in rows], jnp.float32))
            assert int(got[0]) == want, (name, seed)


def test_sample_rows_with_a_mask_is_pick_ref_over_the_masked_logits():
    """The constrained-decoding mask goes in before everything, as before:
    rows of every class in one batch, fewer allowed ids than candidates."""
    v = 9_001
    logits = jax.random.normal(jax.random.PRNGKey(8), (5, v), jnp.float32) * 3.0
    mask = np.zeros((5, v), bool)
    rng = np.random.default_rng(5)
    for i in range(5):
        mask[i, rng.choice(v, size=17 + 30 * i, replace=False)] = True
    seeds = jnp.arange(40, 45, dtype=jnp.int32)
    steps = jnp.arange(5, dtype=jnp.int32)
    temp, tk, tp = (jnp.asarray(c) for c in zip(*SETTINGS))
    gumbel = jax.vmap(lambda s, t: jax.random.gumbel(
        jax.random.fold_in(jax.random.PRNGKey(s), t), (v,), jnp.float32))(seeds, steps)
    got = sample_rows(logits, seeds, steps, temp, tk, tp, mask=jnp.asarray(mask))
    want = _pick_ref(jnp.where(jnp.asarray(mask), logits, _NEG_INF), gumbel, temp, tk, tp)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert all(mask[i, int(t)] for i, t in enumerate(np.asarray(got)))


@pytest.mark.parametrize("temp,tk,tp,want", [
    (0.0, 0, 1.0, "greedy"), (0.0, 40, 0.9, "greedy"), (0.8, 40, 1.0, "restricted"),
    (0.8, 0, 0.95, "restricted"), (0.8, 0, 1.0, "unrestricted"),
    (0.8, 300, 1.0, "unrestricted"),  # a top-k of the whole vocabulary restricts nothing
])
def test_row_class_is_the_samplers_own_split(temp, tk, tp, want):
    assert row_class(temp, tk, tp, 256) == want


@async_test
async def test_batcher_counts_admitted_rows_by_sampler_class(model):
    """Three integers in the batcher's stats: what share of rows never need
    the whole-vocabulary draw."""
    cfg, params = model
    b = ContinuousBatcher(params, cfg, max_slots=4, max_seq_len=64, buckets=[8, 64])
    try:
        async def run(prompt, **kw):
            sp = SamplingParams(max_tokens=3, seed=1, **kw)
            return [t async for t in b.submit(prompt, sp)]

        await asyncio.gather(
            run([1, 2, 3], temperature=0.0),
            run([4, 5, 6], temperature=0.0, top_k=5),
            run([7, 8], temperature=0.8, top_k=5),
            run([9, 8, 7], temperature=0.8, top_p=0.9),
            run([3, 3], temperature=0.8),
        )
        await run([2, 2, 2], temperature=0.7, top_k=cfg.vocab_size)
        snap = b.stats.snapshot()
        assert (snap["rows_greedy"], snap["rows_restricted"], snap["rows_unrestricted"]) \
            == (2, 2, 2)
        assert snap["requests"] == 6
        assert b.stats.sampler_counters() == {"greedy": 2, "restricted": 2, "unrestricted": 2}
    finally:
        b.stop()
