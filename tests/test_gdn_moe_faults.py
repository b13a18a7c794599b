"""Faults put into the linear-attention / gated-attention family on purpose:
each must fail the toy limits of ``tests/test_gdn_moe.py`` by a wide margin,
through prefill, the chunked rule's carry and six paged decode steps against
the plain reference. A fault in the delta rule is put into both of its forms
(the chunked prefill and the decode step), built from one token-by-token step
that is wrong in the named way. A file of its own so that ``--dist loadfile``
gives it a worker of its own."""

import jax
import jax.numpy as jnp
import pytest
from test_gdn_moe import check, model, prompt, serve  # noqa: F401 — fixtures

from nats_llm_studio_tpu.models import experts
from nats_llm_studio_tpu.ops import gated_delta, ssm_scan


def _rule(step):
    """(chunked form, decode step) that run ``step(s, q, k, v, alpha, beta)
    -> (s, o)`` a token at a time, with the sound forms' signatures."""
    def chunked(q, k, v, log_alpha, beta, s0, chunk=None):
        def one(s, xs):
            qt, kt, vt, gt, bt = xs
            return step(s, qt, kt, vt, jnp.exp(gt), bt)

        xs = tuple(jnp.moveaxis(z.astype(jnp.float32), 1, 0)
                   for z in (q, k, v, log_alpha, beta))
        s, o = jax.lax.scan(one, s0, xs)
        return jnp.moveaxis(o, 0, 1), s

    def decode(pool, layer, live, decay, beta, q, k, v):
        # the kernel's operands: a key head once for the value heads it
        # serves, the read-out through the gated norm (``gated_delta.Values``)
        s = pool[:, layer]
        rep = s.shape[1] // q.shape[1]
        s1, o = step(s, jnp.repeat(q, rep, axis=1), jnp.repeat(k, rep, axis=1), v.v, decay, beta)
        on = live.mask[:, None, None, None]
        y = gated_delta.gated_norm(o, v.zs[:, -o.shape[1] * o.shape[2]:], v.gain[layer], v.eps[0])
        return (pool.at[:, layer].set(jnp.where(on, s1, s)),
                jnp.where(live.mask[:, None], y, jnp.zeros((), y.dtype)))

    return chunked, decode


def _read(s, x):
    return jnp.einsum("...hkv,...hk->...hv", s, x)


def _write(k, u):
    return k[..., :, None] * u[..., None, :]


def _read_after_the_write(s, q, k, v, a, b):
    # the plain write first, then the read of what is already there
    s = a[..., None, None] * s
    written = s + _write(k, b[..., None] * v)
    s = s + _write(k, b[..., None] * (v - _read(written, k)))
    return s, _read(s, q)


def _no_beta(s, q, k, v, a, b):
    s = a[..., None, None] * s
    s = s + _write(k, v - _read(s, k))
    return s, _read(s, q)


def _decay_after_the_update(s, q, k, v, a, b):
    s = a[..., None, None] * (s + _write(k, b[..., None] * (v - _read(s, k))))
    return s, _read(s, q)


def _patch_rule(step):
    def patch(monkeypatch):
        chunked, decode = _rule(step)
        monkeypatch.setattr(gated_delta, "gated_delta_chunked", chunked)
        monkeypatch.setattr(gated_delta, "gated_delta_step_auto", decode)
    return patch


def _tail_one_short(monkeypatch):
    sound = ssm_scan.causal_conv

    def short(xbc, tail, w, b, valid):
        out, _ = sound(xbc, tail, w, b, valid)
        return out, sound(xbc, tail, w, b, jnp.maximum(valid - 1, 0))[1]

    monkeypatch.setattr(ssm_scan, "causal_conv", short)


def _not_renormalised(monkeypatch):
    sound = experts.route

    def raw(h, p, cfg):
        idx, gate = sound(h, p, cfg)
        logits = jnp.einsum("btd,de->bte", h.astype(jnp.float32), p["router"].astype(jnp.float32))
        return idx, jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), idx, axis=-1)

    monkeypatch.setattr(experts, "route", raw)


def _sigmoid_scores(monkeypatch):
    def sigmoid(h, p, cfg):
        logits = jnp.einsum("btd,de->bte", h.astype(jnp.float32), p["router"].astype(jnp.float32))
        score = jax.nn.sigmoid(logits)
        chosen, idx = jax.lax.top_k(score, cfg.n_experts_used)
        return idx, chosen / jnp.sum(chosen, axis=-1, keepdims=True)

    monkeypatch.setattr(experts, "route", sigmoid)


def _gate_on_the_wrong_half(params):
    attn = dict(params["blocks"]["attn"])
    half = attn["wq"].shape[-1] // 2
    attn["wq"] = jnp.concatenate([attn["wq"][..., half:], attn["wq"][..., :half]], axis=-1)
    return dict(params, blocks=dict(params["blocks"], attn=attn))


FAULTS = {
    "the state read after the write instead of before": dict(patch=_patch_rule(_read_after_the_write)),
    "beta left out": dict(patch=_patch_rule(_no_beta)),
    "the decay applied after the update": dict(patch=_patch_rule(_decay_after_the_update)),
    "the convolution tail one short": dict(patch=_tail_one_short, how=dict(chunks=(17, 17, 6))),
    "the output gate on the wrong half of wq": dict(params=_gate_on_the_wrong_half),
    "rotary on all of a head's dims": dict(cfg=dict(rope_dim=0)),
    "sigmoid scores in the router": dict(patch=_sigmoid_scores),
    "the picks not renormalised": dict(patch=_not_renormalised),
    "the shared expert's gate left out": dict(cfg=dict(shared_gate=False)),
}


@pytest.mark.parametrize("name", list(FAULTS))
def test_a_fault_put_in_on_purpose_fails_the_toy_limits(model, prompt, name, monkeypatch):  # noqa: F811
    cfg, params = model
    how = FAULTS[name]
    if "patch" in how:
        how["patch"](monkeypatch)
    served = serve(cfg.with_(**how.get("cfg", {})), how.get("params", lambda p: p)(params),
                   prompt, 6, **how.get("how", {}))
    monkeypatch.undo()
    out = check(params, prompt, served)
    d = out["decoded"]
    worst = max(d["median_abs_diff"] / d["median_tolerance"],
                d["max_abs_diff"] / d["token_tolerance"])
    assert not out["ok"] and worst > 5, (name, out)
    print(f"\n{name}: decoded median {d['median_abs_diff']:.3f}, max {d['max_abs_diff']:.3f}")
