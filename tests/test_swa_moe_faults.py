"""Faults put into the window / full attention family on purpose: each must
fail the toy limits of ``tests/test_swa_moe.py`` (whose toy, driver and
limits these are), by more than five times. A file of its own so that
``--dist loadfile`` gives it a worker of its own: on an empty compile cache
the family's tests are the longest file of tier 1."""

import jax.numpy as jnp
import pytest
from test_swa_moe import (  # noqa: F401 — ``model`` and ``prompt`` are fixtures
    CONF, PROMPT, REF, SLOT, TOY_DECODED, TOY_FIRST, WINDOW, check, decode, empty_pools, entry,
    into_pool, model, prefill, prompt, serve, tokens)

from benchmark.lib import correct
from nats_llm_studio_tpu.models import swa_moe
from nats_llm_studio_tpu.ops.kvcache import WithState, state_row, state_write_row

# -- faults put in on purpose ------------------------------------------------


def _window_off_by_one(monkeypatch):
    sound = swa_moe.window_attention
    monkeypatch.setattr(swa_moe, "window_attention",
                        lambda q, k, v, start, window, scale: sound(q, k, v, start, window + 1, scale))


def _ring_written_before_it_is_read(monkeypatch):
    """The chunk's keys land in the ring first, and the queries then read the
    ring as if it still held the keys before the chunk."""
    sound = swa_moe.window_attention

    def attend(q, keys, values, start, window, scale):
        t = keys.shape[2] - window
        valid = jnp.full((keys.shape[0],), t, jnp.int32)
        late = [jnp.concatenate([swa_moe.ring_in_order(
            swa_moe.ring_after(x, start, valid, window), start), x[:, :, window:]], axis=2)
            for x in (keys, values)]
        return sound(q, late[0], late[1], start, window, scale)

    monkeypatch.setattr(swa_moe, "window_attention", attend)


def _rotary_sets_swapped(monkeypatch):
    """Each kind rotates by the other kind's table (cut or repeated to its own
    rotary dims, so that the shapes still fit)."""
    sound = swa_moe.rope_tables

    def swapped(cfg, positions):
        t = sound(cfg, positions)
        return {"full": _resized(t["window"], t["full"][2]),
                "window": _resized(t["full"], t["window"][2])}

    monkeypatch.setattr(swa_moe, "rope_tables", swapped)


def _resized(table, dims):
    cos, sin, _ = table
    half = dims // 2
    reps = -(-half // cos.shape[-1])
    return (jnp.tile(cos, reps)[..., :half], jnp.tile(sin, reps)[..., :half], dims)


def _gate_left_out(monkeypatch):
    monkeypatch.setattr(swa_moe, "_attn_out",
                        lambda o, gate, p, sound=swa_moe._attn_out: sound(o, None, p))


def _another_slots_ring(cfg, params, prompt):
    """The slot decodes on the ring of a slot that holds another prompt."""
    _, other = prefill(cfg, params, tokens(77, PROMPT))
    logits, rows = prefill(cfg, params, prompt)
    kp, vp = into_pool(into_pool(empty_pools(cfg), rows), other, slot=0)
    swapped = tuple(
        WithState(p.kv, state_write_row(p, state_row(p, 0), SLOT), p.axes) for p in (kp, vp))
    return decode(cfg, params, swapped, entry(logits), len(prompt), 5)[0]


def _stale_ring(cfg, params, prompt):
    """The admit writes the slot's KV and leaves the ring of the slot's
    previous request where it was."""
    _, old = prefill(cfg, params, tokens(78, PROMPT))
    logits, rows = prefill(cfg, params, prompt)
    pools = into_pool(into_pool(empty_pools(cfg), old), rows, with_ring=False)
    return decode(cfg, params, pools, entry(logits), len(prompt), 5)[0]


def _heads_swapped(cfg, params, prompt):
    """The window layers run with the full layers' head count and the full
    layers with the window layers': each reads the first columns of its wq
    and rows of its wo as if they were all of them."""
    def cut(stack, heads):
        d = cfg.head_dim
        return dict(stack, wq=stack["wq"][..., : heads * d], wo=stack["wo"][:, : heads * d],
                    wg=stack["wg"][..., :heads])

    blocks = dict(params["blocks"])
    blocks["win"] = cut(blocks["win"], cfg.n_heads)      # 6 -> 4 heads
    bad = cfg.with_(win_n_heads=cfg.n_heads)
    return serve(bad, dict(params, blocks=blocks), prompt, 6)


FAULTS = {
    "a window one key too wide": dict(patch=_window_off_by_one, how=dict(chunks=(20, 20))),
    "the ring written before it is read": dict(
        patch=_ring_written_before_it_is_read, how=dict(chunks=(20, 20))),
    "the two rotary sets swapped": dict(patch=_rotary_sets_swapped),
    "the gate left out": dict(patch=_gate_left_out),
    "head counts swapped": dict(serve=_heads_swapped),
    "a slot decodes on another slot's ring": dict(serve=_another_slots_ring),
    "ring left stale from the slot's previous request": dict(serve=_stale_ring),
    "a padded position gets into the ring": dict(how=dict(pad=24, mask_padding=False)),
    "no selection bias": dict(params=lambda p: dict(p, blocks=dict(p["blocks"], moe=dict(
        p["blocks"]["moe"], e_bias=jnp.zeros_like(p["blocks"]["moe"]["e_bias"]))))),
    "YaRN left out of the full layers": dict(cfg=dict(rope_factor=1.0, rope_attn_factor=1.0)),
    "all of a head rotated in the full layers": dict(cfg=dict(rope_dim=0)),
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
                d["max_abs_diff"] / d["token_tolerance"],
                out["max_abs_diff"] / TOY_FIRST["token_tol"])
    assert not out["ok"] and worst > 5, (name, out)
    print(f"\n{name}: decoded median {d['median_abs_diff']:.3f}, max {d['max_abs_diff']:.3f}")


def test_the_decode_kernel_sees_a_window_of_16_keys_and_not_17(model, prompt):
    """The window counts the query's own key: the ring kernel against the
    reference with ``sliding_window`` 17 must fail, as the reference with 16
    passes (the decode side of "a window one key too wide")."""
    cfg, params = model
    entries = serve(cfg, params, prompt, 6)
    toks = correct.served_tokens(entries)
    wide = REF.tail_logprobs(params, dict(CONF, sliding_window=WINDOW + 1),
                             list(prompt) + toks[:-1], len(toks))
    out = correct.compare_probes([(wide, entries)], TOY_FIRST, TOY_DECODED)
    assert not out["ok"] and out["decoded"]["max_abs_diff"] > 5 * TOY_DECODED["token_tol"], out
