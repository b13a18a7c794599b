"""``correct`` over generated positions, on the CPU at a tiny size. The served
side is the program's own prefill (``models.llama.forward``) followed by
``forward_decode_paged`` steps over a pool and a block table, driven by this
test the way the batcher drives them; the reference is the plain one, run once
over the prompt and the served tokens. The sound path passes. Broken on
purpose three ways (position off by one, one block-table entry swapped, one
KV block left stale) the decoded positions fail by a wide margin while the
first token, which comes out of the prefill, still passes."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run
from benchmark.lib import correct, weights

CONF = json.loads((Path(__file__).parent / "rehearsal" / "configs" / "tiny-granite.json").read_text())
CONF = dict(CONF, num_hidden_layers=3)
REF = run.load_module(run.BENCH / "references" / "granite_dense.py")

T, SEQ = 16, 128            # pool block tokens; a slot's table spans SEQ
PROMPT = 40                 # not a multiple of T: the decode crosses into a new block at 48
TABLE = [3, 5, 2, 7, 1, 4, 6, 8]   # the slot's blocks, in no order the pool has

# The chip's tolerances stand well clear of the noise measured there (see
# lib/correct.py). The toy's noise is bf16 through 3 tiny layers: the same
# rule gives it these.
TOY_FIRST = {"median_tol": 0.25, "token_tol": 0.6}
TOY_DECODED = {"median_tol": 0.25, "token_tol": 0.9, "gap_tol": 0.6}


@pytest.fixture(scope="module")
def model():
    # at d 64, N(0, 0.02) blocks add nothing to a stream the x12 embedding
    # fills: draw at 1/sqrt(d), as test_reference.py does
    mp = pytest.MonkeyPatch()
    mp.setattr(weights, "INIT_STD", 0.125)
    try:
        from nats_llm_studio_tpu.parallel.mesh import build_mesh

        cfg = REF.model_config(CONF, SEQ)
        mesh = build_mesh({"tp": 1}, devices=jax.local_devices()[:1])
        yield cfg, weights.make_seeded_params(4321)(None, cfg, mesh, quant="int8")
    finally:
        mp.undo()


def entry(logits) -> dict:
    """What a reply's ``logprobs.content`` holds for one served token."""
    lp = np.asarray(jax.nn.log_softmax(jnp.asarray(logits, jnp.float32)))
    tok = int(np.argmax(lp))

    def one(i):
        return {"token": chr(int(i)), "bytes": [int(i)], "logprob": float(lp[i])}

    return dict(one(tok), top_logprobs=[one(i) for i in np.argsort(-lp)[:correct.TOP_K]])


def serve(model, prompt, n, pos_shift=0, swap=None, stale=None):
    """Prefill ``prompt`` into the pool through the slot's table, then decode
    n-1 greedy tokens with ``forward_decode_paged``. The breaks: decode at
    position + ``pos_shift``; table entries ``swap`` exchanged after the
    prefill; block ``stale`` of the table not written by this prompt's
    prefill (it holds another prompt's rows)."""
    from nats_llm_studio_tpu.models.llama import forward, forward_decode_paged, make_cache
    from nats_llm_studio_tpu.ops.kvcache import kv_pool_scatter_view, kv_pool_zeros

    cfg, params = model
    tbl = jnp.asarray([TABLE], jnp.int32)
    nb = len(TABLE)
    shape = (1 + 2 * nb, cfg.n_layers, cfg.n_kv_heads, T, cfg.head_dim)
    kp = kv_pool_zeros(shape, jnp.dtype(cfg.dtype))
    vp = kv_pool_zeros(shape, jnp.dtype(cfg.dtype))

    def prefill(tokens, kp, vp, blocks):
        k, v = make_cache(cfg, 1, SEQ)
        logits, k, v = forward(params, cfg, jnp.asarray([tokens], jnp.int32), k, v,
                               jnp.zeros((1,), jnp.int32))
        vb = jnp.asarray([blocks], jnp.int32)
        return (logits[0, len(tokens) - 1], kv_pool_scatter_view(kp, k, tbl, vb),
                kv_pool_scatter_view(vp, v, tbl, vb))

    every = list(range(nb))
    if stale is not None:
        other = list(np.random.default_rng(9).integers(32, 127, size=len(prompt)))
        _, kp, vp = prefill(other, kp, vp, every)
    logits, kp, vp = prefill(prompt, kp, vp, [b for b in every if b != stale])
    if swap:
        i, j = swap
        order = list(TABLE)
        order[i], order[j] = order[j], order[i]
        tbl = jnp.asarray([order], jnp.int32)
    entries = [entry(logits)]
    pos = len(prompt)
    step = jax.jit(lambda tok, kp, vp, pos: forward_decode_paged(
        params, cfg, tok, kp, vp, tbl, pos))
    for _ in range(n - 1):
        tok = jnp.asarray([[entries[-1]["bytes"][0]]], jnp.int32)
        logits, kp, vp = step(tok, kp, vp, jnp.asarray([pos + pos_shift], jnp.int32))
        entries.append(entry(logits[0, -1]))
        pos += 1
    return entries


def check(model, prompt, entries) -> dict:
    _, params = model
    toks = correct.served_tokens(entries)
    ref = REF.tail_logprobs(params, CONF, list(prompt) + toks[:-1], len(toks))
    return correct.compare_probes([(ref, entries)], TOY_FIRST, TOY_DECODED)


@pytest.fixture(scope="module")
def prompt():
    return [int(t) for t in np.random.default_rng(1).integers(32, 127, size=PROMPT)]


def test_the_sound_path_passes_first_and_decoded(model, prompt):
    out = check(model, prompt, serve(model, prompt, correct.DECODE_TOKENS))
    assert out["ok"] and out["first_ok"] and out["decoded"]["ok"], out
    assert out["decoded"]["positions"] == correct.DECODE_TOKENS - 1
    assert out["decoded"]["max_abs_diff"] < 0.45 and out["decoded"]["gap_max"] < 0.3, out


@pytest.mark.parametrize("name,how", [
    ("position off by one", {"pos_shift": 1}),
    # with a block past the frontier: two full blocks inside it may change
    # places unseen, since a key carries its own rotary position and softmax
    # attention does not care in which order it meets the keys
    ("one block-table entry swapped", {"swap": (1, 5)}),
    ("one KV block left stale", {"stale": 1}),
])
def test_a_broken_decode_fails_the_decoded_positions_only(model, prompt, name, how):
    out = check(model, prompt, serve(model, prompt, correct.DECODE_TOKENS, **how))
    d = out["decoded"]
    assert out["first_ok"], (name, out)          # the prefill is sound
    assert not d["ok"] and not out["ok"], (name, out)
    # by a wide margin: over twice the median's limit and, for the number
    # that fails furthest, over five times
    assert d["median_abs_diff"] > 2 * TOY_DECODED["median_tol"], (name, d)
    assert max(d["median_abs_diff"] / d["median_tolerance"], d["max_abs_diff"] / d["token_tolerance"],
               d["gap_max"] / d["gap_tolerance"]) > 5, (name, d)
    print(f"\n{name}: decoded median {d['median_abs_diff']:.3f} "
          f"(limit {TOY_DECODED['median_tol']}), max {d['max_abs_diff']:.3f} "
          f"(limit {TOY_DECODED['token_tol']}), gap {d['gap_max']:.3f} "
          f"(limit {TOY_DECODED['gap_tol']})")


def test_a_token_altered_where_it_is_produced_fails_the_gap(model, prompt):
    """The served distribution is right and the served token is not its
    argmax: only the gap sees it."""
    entries = serve(model, prompt, correct.DECODE_TOKENS)
    second = entries[5]["top_logprobs"][4]
    entries[5] = dict(entries[5], token=second["token"], bytes=second["bytes"])
    _, params = model
    toks = correct.served_tokens(entries)
    # teacher-forced on what was "served": rows after 5 follow the altered token
    ref = REF.tail_logprobs(params, CONF, list(prompt) + toks[:-1], len(toks))
    d = correct.compare_probes([(ref, entries)], TOY_FIRST, TOY_DECODED)["decoded"]
    assert d["gap_max"] > 3 * TOY_DECODED["gap_tol"] and not d["ok"], d


def test_compared_names_each_number_beside_its_limit(model, prompt):
    out = check(model, prompt, serve(model, prompt, 4))
    lines = correct.compared(out)
    assert len(lines) == 6 and all(line.startswith("reference ") for line in lines)
    assert sum("<=" in line for line in lines) == 5 and not any("FAILS" in line for line in lines)


# -- the control: the reference a precision lower, in the served path's place --

def test_padding_to_one_program_changes_no_row(model, prompt):
    _, params = model
    toks = list(prompt) + [40, 41, 42, 43, 44]
    plain = REF.tail_logprobs(params, CONF, toks, 5)
    padded = REF.tail_logprobs(params, CONF, toks, 5, pad_to=(64, 8))
    assert padded.shape == plain.shape
    np.testing.assert_allclose(padded, plain, atol=2e-5)
    with pytest.raises(ValueError, match="do not fit"):
        REF.tail_logprobs(params, CONF, toks, 5, pad_to=(32, 8))


def test_the_lower_precision_control_fails_where_the_served_path_passes(model, prompt):
    """fp8 activations and KV, the step below the bf16 the configuration
    serves in: every number the probes' check reads of it is over three times
    the served path's, and the check says no."""
    _, params = model
    entries = serve(model, prompt, correct.DECODE_TOKENS)
    toks = correct.served_tokens(entries)
    full = list(prompt) + toks[:-1]
    ref = REF.tail_logprobs(params, CONF, full, len(toks))
    low = REF.tail_logprobs(params, CONF, full, len(toks), lower="fp8")
    sound = correct.compare_probes([(ref, entries)], TOY_FIRST, TOY_DECODED)
    ctl = correct.compare_probes([(ref, correct.entries_of(low))], TOY_FIRST, TOY_DECODED)
    assert sound["ok"] and not ctl["ok"] and not ctl["decoded"]["ok"], (sound, ctl)
    assert ctl["decoded"]["median_abs_diff"] > 3 * sound["decoded"]["median_abs_diff"]
    assert ctl["decoded"]["max_abs_diff"] > 3 * sound["decoded"]["max_abs_diff"]
    # the window's rule needs ids alone: the token the control puts first
    win_sound = correct.compare_window([(ref, toks)], gap_tol=0.6, mean_tol=0.05)
    win_ctl = correct.compare_window([(ref, [int(i) for i in low.argmax(-1)])],
                                     gap_tol=0.6, mean_tol=0.05)
    print(f"\nsound {sound['decoded']} {win_sound}\ncontrol {ctl['decoded']} {win_ctl}")
    assert win_sound["ok"]


def test_the_windows_sample_holds_the_longest_and_stops_at_its_budget():
    from benchmark.lib.traffic import Record

    def rec(idx, n_prompt, n_out, temperature=0.0, t_done=5.0, ok=True):
        return Record(idx, n_prompt, n_out, 1.0, t_done=t_done, ok=ok, temperature=temperature,
                      text="x" * n_out)

    recs = [rec(i, 100 + i, 60) for i in range(10)] + [
        rec(10, 900, 200), rec(11, 999, 256, temperature=0.8), rec(12, 999, 256, t_done=50.0),
        rec(13, 999, 256, ok=False)]
    got = correct.window_sample(recs, 2.0, 32.0, seed=7)
    assert got[0].idx == 10                                  # the longest greedy one that finished
    assert all(r.temperature == 0.0 and r.ok and r.t_done < 32.0 for r in got)
    assert sum(r.max_tokens for r in got[:-1]) < correct.WINDOW_SAMPLE_TOKENS <= sum(
        r.max_tokens for r in got)
    assert [r.idx for r in got] == [r.idx for r in correct.window_sample(recs, 2.0, 32.0, seed=7)]
    assert [r.idx for r in got] != [r.idx for r in correct.window_sample(recs, 2.0, 32.0, seed=8)]
    assert len(correct.window_sample([rec(i, 50, 8) for i in range(40)], 2.0, 32.0, 1)) \
        == correct.WINDOW_SAMPLE_MAX
    # a reply whose bytes are not its token ids cannot be held to anything
    odd = rec(0, 50, 8)
    odd.text = "\u00e9" * 8
    assert correct.window_sample([odd], 2.0, 32.0, 1) == []
    empty = correct.compare_window([])
    assert not empty["ok"]                                   # nothing to compare is not a pass
