"""Token sampling: temperature / top-k / top-p, fully vectorized per row.

Per-request parameters are arrays of shape [B] so one jitted decode step can
serve a continuously-batched set of requests with different sampling settings
(SURVEY.md §7: the batcher is on the critical perf path).

Sort-free design: a full-vocab ``sort``+``argsort`` costs several ms per
decode step on TPU (measured ~7 ms at V=49k — comparable to reading all the
model weights). Instead:

* greedy and unrestricted temperature sampling use ``argmax`` /
  Gumbel-max over the full vocab — exact, no sort;
* top-k / top-p restricted rows draw from the top ``CANDIDATES`` logits
  (``top_candidates``, cheap at fixed small k). top-k above the cap and top-p
  nuclei wider than the cap are truncated to the cap — for peaked LLM
  distributions the mass beyond the top 64 is negligible, and serving
  engines routinely apply the same candidate cap.

What a row costs in ``sample_rows`` (the served path), by what it asks for:

* a greedy row (temperature <= 0): one pass over its logits, the candidates'
  first id where a restricted row shares the batch, one ``argmax`` where none;
* a restricted row (0 < top_k < V or top_p < 1): one streaming pass for the
  maxima of its groups of 128 logits, then ``top_k`` over a thousand values
  three times (``top_candidates``: the maxima, the maxima of the groups of 16
  in the 64 groups kept, the 1,024 logits left), and 64 Gumbel values: the
  noise is computed at the candidates' ids alone (``gumbel_at``), bit for
  bit what the whole-vocabulary draw holds there;
* an unrestricted sampled row (temperature > 0, no top-k, top_p 1): V Gumbel
  values and an ``argmax`` over V, run (``lax.cond``) only on a step whose
  batch holds such a row; the candidates only on a step that holds a
  restricted sampled row.

A row's token depends on its own inputs alone, whichever branches its batch
ran, and is the token ``_pick`` gives over the row's whole draw: a request
replayed under its seed keeps its completion.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.random import threefry_2x32

CANDIDATES = 64  # static candidate cap for restricted (top-k/top-p) rows
# the candidates' funnel keeps the CANDIDATES groups of largest maximum, of
# 128 consecutive ids (a vector of lanes), then of 16 among those; a stage is
# skipped where it would keep the whole row. On a v5e at 16 x 100,352, beside
# the 18 us a pass over the rows costs: lax.top_k over the row 327 us, one
# stage 98 us, both 43 us (PERF.md section 6, PR 43)
FUNNEL = (128, 16)
_NEG_INF = jnp.float32(-jnp.inf)


def _at(table: jax.Array, idx: jax.Array) -> jax.Array:
    """``take_along_axis(table, idx, -1)`` for a table of a few dozen columns,
    as a compare and a sum: a gather of a thousand scalars costs the TPU more
    than the candidates' whole softmax."""
    hit = idx[:, :, None] == jnp.arange(table.shape[1])[None, None, :]
    return jnp.sum(jnp.where(hit, table[:, None, :], 0), axis=-1)


def top_candidates(logits: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``lax.top_k(logits, min(CANDIDATES, V))`` over the last axis, value for
    value and id for id, through a funnel where the vocabulary is wide: the
    row is cut into groups of consecutive ids, the C groups of largest maximum
    are kept in id order (``top_k`` over the maxima, one streaming pass to
    make them), and the same is done to what is left with narrower groups;
    ``top_k`` then sorts 1,024 logits and not 100,352. Each of the C largest
    logits lies in one of the C groups of largest maximum (a group ahead of
    its own holds a logit ahead of it), and every stage gives equals to the
    lower id, as ``lax.top_k`` does: kept groups stay in id order, so a lower
    position is a lower id. The shape alone decides the stages."""
    b, v = logits.shape
    c = min(CANDIDATES, v)
    stages = [g for g in FUNNEL if v > c * g]
    if not stages:
        return jax.lax.top_k(logits, c)
    pool = jnp.pad(logits, ((0, 0), (0, -v % stages[0])), constant_values=_NEG_INF)
    # position p of the pool holds id starts[p // run] + p % run
    starts, run = jnp.zeros((b, 1), jnp.int32), pool.shape[1]
    for g in stages:
        grouped = pool.reshape(b, -1, g)
        _, kept = jax.lax.top_k(grouped.max(axis=-1), c)
        kept = jnp.sort(kept, axis=-1)
        pool = jnp.take_along_axis(grouped, kept[:, :, None], axis=1).reshape(b, c * g)
        starts, run = _at(starts, kept // (run // g)) + kept % (run // g) * g, g
    cand, at = jax.lax.top_k(pool, c)
    return cand, _at(starts, at // run) + at % run


def require_partitionable_threefry() -> None:
    """``gumbel_at`` reproduces ONE layout of ``jax.random.gumbel``'s bits:
    element i of a draw is ``threefry_2x32(key, (0, i))``. With
    ``jax_threefry_partitionable`` off, or the high-dynamic-range Gumbel on,
    the whole draw is laid out otherwise and a replayed request would draw
    other tokens: refuse to build a program then."""
    cfg = jax.config
    if (not cfg.jax_threefry_partitionable or cfg.jax_default_prng_impl != "threefry2x32"
            or cfg.jax_high_dynamic_range_gumbel):
        raise RuntimeError("the sampler needs jax_threefry_partitionable, threefry2x32 keys "
                           "and the low-dynamic-range Gumbel draw: another layout of the "
                           "draw's bits would change the tokens a seed gives")


def gumbel_at(key: jax.Array, ids: jax.Array) -> jax.Array:
    """``jax.random.gumbel(key, (V,), float32)[ids]`` for any V above the ids,
    bit for bit, at the cost of ``ids.size`` values: the bits of element i are
    the two words of ``threefry_2x32(key, (0, i))`` xor'd, and the rest is
    ``jax.random``'s own arithmetic (``_uniform`` over [tiny, 1), then
    ``-log(-log(u))``), operation for operation."""
    require_partitionable_threefry()
    lo = ids.astype(jnp.uint32).ravel()
    words = threefry_2x32(jax.random.key_data(key), jnp.concatenate([jnp.zeros_like(lo), lo]))
    bits = (words[: lo.size] ^ words[lo.size:]).reshape(ids.shape)
    info = jnp.finfo(jnp.float32)
    one = np.float32(1.0)
    mantissa = jax.lax.shift_right_logical(bits, jnp.uint32(info.bits - info.nmant))
    floats = jax.lax.bitcast_convert_type(mantissa | one.view(np.uint32), jnp.float32) - one
    u = jnp.maximum(info.tiny, floats * (one - info.tiny) + info.tiny)
    return -jnp.log(-jnp.log(u))


def _params(b, temperature, top_k, top_p):
    temperature = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (b,))
    top_k = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), (b,))
    top_p = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), (b,))
    return temperature, top_k, top_p, jnp.maximum(temperature, 1e-6)[:, None]


def _candidate_pick(cand, cand_idx, g_cand, safe_t, top_k, top_p) -> jax.Array:
    """A restricted row's draw among its candidates (sorted desc, [B, C]),
    ``g_cand`` the Gumbel noise at their ids."""
    c = cand.shape[-1]
    ranks = jnp.arange(c)[None, :]
    k_eff = jnp.where(top_k <= 0, c, jnp.minimum(top_k, c))[:, None]
    keep = ranks < k_eff
    # top-p over the candidate softmax; always keep the first token that
    # crosses p (so the nucleus is never empty)
    probs = jax.nn.softmax(cand / safe_t, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep &= (cum - probs) < top_p[:, None]
    masked = jnp.where(keep, cand / safe_t, _NEG_INF)
    drawn = jnp.argmax(masked + g_cand, axis=-1)
    return jnp.take_along_axis(cand_idx, drawn[:, None], axis=-1)[:, 0]


def _restricted(top_k, top_p, v):
    return ((top_k > 0) & (top_k < v)) | (top_p < 1.0)


def row_class(temperature: float, top_k: int, top_p: float, vocab_size: int) -> str:
    """What a row asks of ``sample_rows``, on the host: "greedy", "restricted"
    (it picks among its candidates) or "unrestricted" (it needs the
    whole-vocabulary draw)."""
    if temperature <= 0.0:
        return "greedy"
    return "restricted" if _restricted(top_k, top_p, vocab_size) else "unrestricted"


def _pick(logits, gumbel, temperature, top_k, top_p, mask=None) -> jax.Array:
    """Shared sort-free selection. gumbel: [B, V] standard Gumbel noise.

    ``mask`` (optional [B, V] bool) bans tokens BEFORE truncation: banned
    logits drop to -inf, so greedy argmax, full Gumbel-max, and the top-k /
    top-p candidate set all operate on the already-constrained distribution
    (constrained decoding stays distribution-exact over the allowed set).
    ``mask=None`` takes the pre-existing code path untouched — unconstrained
    sampling is bit-identical with or without this feature compiled in."""
    if mask is not None:
        logits = jnp.where(mask, logits, _NEG_INF)
    b, v = logits.shape
    temperature, top_k, top_p, safe_t = _params(b, temperature, top_k, top_p)

    greedy = jnp.argmax(logits, axis=-1)
    # exact unrestricted sampling: argmax(logits/T + G) ~ softmax(logits/T)
    full_pick = jnp.argmax(logits / safe_t + gumbel, axis=-1)

    cand, cand_idx = top_candidates(logits)  # sorted desc [B, C]
    g_cand = jnp.take_along_axis(gumbel, cand_idx, axis=-1)
    cand_pick = _candidate_pick(cand, cand_idx, g_cand, safe_t, top_k, top_p)

    pick = jnp.where(_restricted(top_k, top_p, v), cand_pick, full_pick)
    return jnp.where(temperature <= 0.0, greedy, pick).astype(jnp.int32)


def sample(
    logits: jax.Array,  # [B, V] f32
    key: jax.Array,
    temperature: jax.Array | float = 0.8,
    top_k: jax.Array | int = 0,  # 0 = disabled
    top_p: jax.Array | float = 1.0,
    mask: jax.Array | None = None,  # [B, V] bool — False bans the token
) -> jax.Array:
    """Returns sampled token ids [B] int32. temperature <= 0 means greedy
    (per row). top-k and top-p are per-row arrays, not static. One key for
    the batch and every path for every row (``_pick`` over a whole draw):
    the reference loops' sampler, not the served path's."""
    gumbel = jax.random.gumbel(key, logits.shape, jnp.float32)
    return _pick(logits, gumbel, temperature, top_k, top_p, mask=mask)


def sample_rows(
    logits: jax.Array,  # [B, V] f32
    seeds: jax.Array,  # [B] int32 — per-row PRNG seed
    steps: jax.Array,  # [B] int32 — per-row step counter
    temperature: jax.Array | float = 0.8,
    top_k: jax.Array | int = 0,
    top_p: jax.Array | float = 1.0,
    mask: jax.Array | None = None,  # [B, V] bool — False bans the token
) -> jax.Array:
    """Per-row deterministic sampling: row i's randomness depends only on
    (seeds[i], steps[i]), never on batch composition — a request replayed
    with the same seed reproduces its completion regardless of what else is
    running in the continuous batch.

    Row i's token is ``_pick``'s over the row's whole draw
    ``jax.random.gumbel(fold_in(PRNGKey(seeds[i]), steps[i]), (V,))``; what
    is computed follows what the batch's rows ask for (module docstring):
    the candidates and their noise under one ``lax.cond`` on "a restricted
    sampled row is here", the whole draw under one on "an unrestricted
    sampled row is here". Not for use under ``vmap``, which would turn the
    conditionals into selects of both branches."""
    if mask is not None:
        logits = jnp.where(mask, logits, _NEG_INF)
    b, v = logits.shape
    temperature, top_k, top_p, safe_t = _params(b, temperature, top_k, top_p)
    keys = jax.vmap(lambda seed, step: jax.random.fold_in(jax.random.PRNGKey(seed), step))(
        seeds, steps)
    sampled = ~(temperature <= 0.0)
    restricted = _restricted(top_k, top_p, v)
    nobody = jnp.zeros((b,), jnp.int32)

    def among_candidates():
        cand, cand_idx = top_candidates(logits)
        g_cand = jax.vmap(gumbel_at)(keys, cand_idx)
        pick = _candidate_pick(cand, cand_idx, g_cand, safe_t, top_k, top_p)
        # lax.top_k and argmax both give equals to the lower id
        return pick.astype(jnp.int32), cand_idx[:, 0].astype(jnp.int32)

    def over_the_vocabulary():
        gumbel = jax.vmap(lambda k: jax.random.gumbel(k, (v,), jnp.float32))(keys)
        # exact unrestricted sampling: argmax(logits/T + G) ~ softmax(logits/T)
        return jnp.argmax(logits / safe_t + gumbel, axis=-1).astype(jnp.int32)

    cand_pick, greedy = jax.lax.cond(
        jnp.any(sampled & restricted), among_candidates,
        lambda: (nobody, jnp.argmax(logits, axis=-1).astype(jnp.int32)))
    full_pick = jax.lax.cond(
        jnp.any(sampled & ~restricted), over_the_vocabulary, lambda: nobody)
    pick = jnp.where(restricted, cand_pick, full_pick)
    return jnp.where(sampled, pick, greedy)


# ---------------------------------------------------------------------------
# speculative decoding: rejection-sampling acceptance (serve/spec.py design)
# ---------------------------------------------------------------------------


def _log_weights(logits, temperature, top_k, top_p, mask=None) -> jax.Array:
    """Full-vocab log-weights ``w`` with softmax(w) equal to the
    distribution ``_pick`` draws from for temperature > 0 rows — same
    CANDIDATES cap, same top-k/top-p truncation rules, token for token.
    Non-selectable tokens sit at -inf. Greedy rows (temperature <= 0) are
    the caller's job: their "distribution" is a point mass at argmax.

    ``mask`` bans tokens before truncation, mirroring ``_pick`` — so spec
    acceptance against a constrained sampler stays distribution-exact.
    ``mask=None`` is the pre-existing code path, bit for bit."""
    if mask is not None:
        logits = jnp.where(mask, logits, _NEG_INF)
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
    # scatter the kept candidates back onto the full vocab axis
    rows = jnp.arange(b)[:, None]
    masked = jnp.full((b, v), _NEG_INF).at[rows, cand_idx].set(
        jnp.where(keep, cand / safe_t, _NEG_INF)
    )
    restricted = (((top_k > 0) & (top_k < v)) | (top_p < 1.0))[:, None]
    return jnp.where(restricted, masked, logits / safe_t)


def spec_accept_rows(
    logits: jax.Array,  # [B, T, V] f32 — verify logits, T = k + 1
    drafts: jax.Array,  # [B, k] int32 — proposed draft tokens
    draft_len: jax.Array,  # [B] int32 — valid drafts per row (0..k)
    seeds: jax.Array,  # [B] int32 — per-row PRNG seed (sample_rows contract)
    steps: jax.Array,  # [B] int32 — per-row step counter at verify position 0
    temperature: jax.Array | float = 0.8,
    top_k: jax.Array | int = 0,
    top_p: jax.Array | float = 1.0,
    mask: jax.Array | None = None,  # [B, T, V] bool — per-position bans
) -> tuple[jax.Array, jax.Array]:
    """Rejection-sampling acceptance for prompt-lookup drafts.

    Position j's model distribution is ``p_j`` = what the plain sampler
    would draw from (``_log_weights``; point mass at argmax for greedy
    rows). The draft proposal is DETERMINISTIC (a point mass at d_j), so
    the Leviathan et al. rule collapses to: accept d_j with probability
    p_j(d_j); on the first rejection, resample from p_j with d_j removed
    and renormalized (the residual (p - min(p, q))+ for a point-mass q);
    when every valid draft is accepted, the bonus token is a PLAIN sample
    from the last position. Each emitted token is therefore distributed
    exactly as the plain sampler's — greedy rows degenerate to "accept
    while the draft equals argmax", which makes greedy output bit-identical
    to non-speculative decoding.

    Randomness: position j consumes the (seeds, steps + j) stream, split
    into an acceptance uniform (fold_in 0) and a residual/bonus Gumbel
    (fold_in 1) — independent per position, independent of batch
    composition. Callers advance the step counter by T per verify.

    Returns ``(tokens [B, T], n_emit [B])``: row b's emitted tokens are
    ``tokens[b, :n_emit[b]]`` (accepted drafts then the resampled/bonus
    token); positions past n_emit hold zeros and carry no meaning.
    """
    if mask is not None:
        # ban before anything downstream: _log_weights truncation, greedy
        # argmax, and residual resampling then all see the constrained
        # distribution (identical to masking inside the plain sampler)
        logits = jnp.where(mask, logits, _NEG_INF)
    b, t, v = logits.shape
    kd = t - 1
    temp_b = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (b,))

    def pos_streams(seed, step):
        def one(j):
            kj = jax.random.fold_in(jax.random.PRNGKey(seed), step + j)
            u = jax.random.uniform(jax.random.fold_in(kj, 0))
            g = jax.random.gumbel(jax.random.fold_in(kj, 1), (v,), jnp.float32)
            return u, g

        return jax.vmap(one)(jnp.arange(t, dtype=jnp.int32))

    u, gumbel = jax.vmap(pos_streams)(seeds, steps)  # [B,T], [B,T,V]
    w = jax.vmap(
        _log_weights, in_axes=(1, None, None, None), out_axes=1
    )(logits, temp_b, top_k, top_p)  # [B, T, V]
    p = jax.nn.softmax(w, axis=-1)
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, T]

    # acceptance over the kd draft positions
    p_draft = jnp.take_along_axis(p[:, :kd], drafts[..., None], axis=-1)[..., 0]
    is_greedy = (temp_b <= 0.0)[:, None]
    ok = jnp.where(is_greedy, drafts == greedy_tok[:, :kd], u[:, :kd] < p_draft)
    ok &= jnp.arange(kd, dtype=jnp.int32)[None, :] < draft_len[:, None]
    # accepted = length of the all-accepted prefix
    a = jnp.cumprod(ok.astype(jnp.int32), axis=1).sum(axis=1)  # [B] in 0..kd

    # the one extra token, from position a: a rejection resamples the
    # residual (draft token masked out); full acceptance samples plainly
    w_a = jnp.take_along_axis(w, a[:, None, None], axis=1)[:, 0]  # [B, V]
    g_a = jnp.take_along_axis(gumbel, a[:, None, None], axis=1)[:, 0]
    greedy_a = jnp.take_along_axis(greedy_tok, a[:, None], axis=1)[:, 0]
    d_a = jnp.take_along_axis(
        drafts, jnp.minimum(a, kd - 1)[:, None], axis=1
    )[:, 0]
    rejected = a < draft_len
    w_res = jnp.where(
        rejected[:, None] & (jnp.arange(v)[None, :] == d_a[:, None]),
        _NEG_INF,
        w_a,
    )
    pick = jnp.argmax(w_res + g_a, axis=-1)
    extra = jnp.where(temp_b <= 0.0, greedy_a, pick).astype(jnp.int32)

    j = jnp.arange(t, dtype=jnp.int32)[None, :]
    drafts_pad = jnp.pad(drafts, ((0, 0), (0, 1)))
    out = jnp.where(j < a[:, None], drafts_pad, 0)
    out = jnp.where(j == a[:, None], extra[:, None], out).astype(jnp.int32)
    return out, (a + 1).astype(jnp.int32)
