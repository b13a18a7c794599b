"""Faults put into the state-space family with layers of latent experts on
purpose: each must fail the toy limits of ``tests/test_ssm_latent_moe.py`` by a
wide margin, through a prefill in three chunks (the scan's carry) and six paged
decode steps against the plain reference. A fault in the scan or in the experts
is put into both of its forms (prefill and the decode step). A file of its own
so that ``--dist loadfile`` gives it a worker of its own."""

import jax
import jax.numpy as jnp
import pytest
from test_ssm_latent_moe import check, model, prompt, serve  # noqa: F401 — fixtures

from nats_llm_studio_tpu.models import experts, ssm_hybrid
from nats_llm_studio_tpu.ops import moe_experts, ssm_scan


def _a_dropped_group(monkeypatch):
    """Every head reads group 0's B and C (the one-group model's scan), in the
    chunked scan and in the state kernel."""
    sound = ssm_hybrid._split_conv

    def first_group(xbc, cfg):
        x, bm, cm = sound(xbc, cfg)
        return x, *(jnp.broadcast_to(z[..., :1, :], z.shape) for z in (bm, cm))

    monkeypatch.setattr(ssm_hybrid, "_split_conv", first_group)


def _the_norm_over_all_groups(monkeypatch):
    """The gated norm over all of d_inner, as the one-group model has it."""
    sound = ssm_hybrid._mixer_out
    monkeypatch.setattr(ssm_hybrid, "_mixer_out",
                        lambda y, x, z, p, cfg: sound(y, x, z, p, cfg.with_(ssm_n_groups=1)))


def _swapped_latent_projections(params):
    moe = dict(params["blocks"]["moe"])
    moe["w_lat_down"], moe["w_lat_up"] = (jnp.swapaxes(moe["w_lat_up"], 1, 2),
                                          jnp.swapaxes(moe["w_lat_down"], 1, 2))
    return dict(params, blocks=dict(params["blocks"], moe=moe))


def _silu_for_relu2(monkeypatch):
    """silu(x W1) W2 in the shared expert and in both kernels."""
    monkeypatch.setattr(experts, "relu2", jax.nn.silu)
    sound = moe_experts._activation

    def silu(x, w_refs, at=slice(None)):
        if len(w_refs) == 2:
            return sound(x, w_refs, at)
        return jax.nn.silu(jnp.dot(x, w_refs[0][:, at], preferred_element_type=jnp.float32))

    monkeypatch.setattr(moe_experts, "_activation", silu)


def _the_shared_expert_in_the_latent(monkeypatch):
    """The shared expert reads what the routed experts read: the latent
    projected back up, not the hidden state."""
    sound = experts._shared
    monkeypatch.setattr(experts, "_shared", lambda h, p, cfg: sound(
        experts.mm(experts.mm(h, p["w_lat_down"]), p["w_lat_up"]), p, cfg))


def _the_bias_weighs(monkeypatch):
    def biased(h, p, cfg):
        logits = jnp.einsum("btd,de->bte", h.astype(jnp.float32), p["router"].astype(jnp.float32))
        score = jax.nn.sigmoid(logits) + p["e_bias"].astype(jnp.float32) * 20
        chosen, idx = jax.lax.top_k(score, cfg.n_experts_used)
        return idx, chosen / jnp.sum(chosen, axis=-1, keepdims=True) * cfg.routed_scaling

    monkeypatch.setattr(experts, "route", biased)


def _tail_one_short(monkeypatch):
    sound = ssm_scan.causal_conv

    def short(xbc, tail, w, b, valid):
        out, _ = sound(xbc, tail, w, b, valid)
        return out, sound(xbc, tail, w, b, jnp.maximum(valid - 1, 0))[1]

    monkeypatch.setattr(ssm_scan, "causal_conv", short)


FAULTS = {
    "a dropped group: every head reads group 0": dict(patch=_a_dropped_group),
    "the gated norm over all the groups at once": dict(patch=_the_norm_over_all_groups),
    "the latent projections swapped": dict(params=_swapped_latent_projections),
    "silu for relu squared": dict(patch=_silu_for_relu2),
    "the shared expert on the latent": dict(patch=_the_shared_expert_in_the_latent),
    "the routed scaling left out": dict(cfg=dict(routed_scaling=1.0)),
    "a selection bias that weighs": dict(patch=_the_bias_weighs),
    "the convolution tail one short": dict(patch=_tail_one_short),
    "rotary embedding in the attention layer": dict(cfg=dict(use_rope=True)),
}


@pytest.mark.parametrize("name", list(FAULTS))
def test_a_fault_put_in_on_purpose_fails_the_toy_limits(model, prompt, name, monkeypatch):  # noqa: F811
    cfg, params = model
    how = FAULTS[name]
    if "patch" in how:
        how["patch"](monkeypatch)
    served = serve(cfg.with_(**how.get("cfg", {})), how.get("params", lambda p: p)(params),
                   prompt, 6, chunks=(17, 17, 6))
    monkeypatch.undo()
    out = check(params, prompt, served)
    d = out["decoded"]
    worst = max(d["median_abs_diff"] / d["median_tolerance"],
                d["max_abs_diff"] / d["token_tolerance"])
    assert not out["ok"] and worst > 5, (name, out)
    print(f"\n{name}: decoded median {d['median_abs_diff']:.3f}, max {d['max_abs_diff']:.3f}")
