"""Faults put into the plain latent-attention block on purpose
(``tests/test_mla_moe_plain.py``'s toy, reference and limits): each must fail
the toy limits by a wide margin, or the reference check would not see it on
the chip either. The prompt is prefilled in three chunks over key blocks of
16, so the blocked chunk attention walks several blocks."""

import jax.numpy as jnp
import pytest
from test_mla_moe_plain import (  # noqa: F401 — fixtures
    CONF, check, key_block_reads, model, prompt, serve)

from nats_llm_studio_tpu.models import mla_moe

SOUND_PROJECT = mla_moe.mla_project


def _one_shared_expert_for_two(params):
    """The second shared expert's rows of the down projection zeroed: what a
    loader that kept ``n_shared_experts`` = 1 would serve."""
    moe = dict(params["blocks"]["moe"])
    half = moe["w_down_s"].shape[1] // 2
    moe["w_down_s"] = moe["w_down_s"].at[:, half:].set(0.0)
    return dict(params, blocks=dict(params["blocks"], moe=moe))


def _dropped_rotary_key(h, p, cfg, cos, sin):
    q_nope, q_rope, c, kr = SOUND_PROJECT(h, p, cfg, cos, sin)
    return q_nope, q_rope, c, jnp.zeros_like(kr)


def _split_swapped(h, p, cfg, cos, sin):
    """A head's query read as [rope | nope] where the model lays it [nope | rope]."""
    b, t, _ = h.shape
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = mla_moe.mm(h, p["wq"]).reshape(b, t, cfg.n_heads, dn + dr)
    _, _, c, kr = SOUND_PROJECT(h, p, cfg, cos, sin)
    return q[..., dr:], mla_moe.apply_rope(q[..., :dr], cos, sin), c, kr


FAULTS = {
    "one shared expert for two": dict(params=_one_shared_expert_for_two),
    "routed_scaling_factor 1 for 2.448": dict(cfg=dict(routed_scaling=1.0)),
    "the rotary key dropped": dict(project=_dropped_rotary_key),
    "the nope / rope split swapped": dict(project=_split_swapped),
    # the chunk attention's key blocks are 16 tokens here: block 1 never read
    # (every later read a block further on), block 0 read again for block 1
    "a key block skipped": dict(at_of=lambda at: jnp.where(at >= 16, at + 16, at)),
    "a key block read twice": dict(at_of=lambda at: jnp.where(at == 16, 0, at)),
}


@pytest.mark.parametrize("name", list(FAULTS))
def test_a_fault_put_in_on_purpose_fails_the_toy_limits(model, prompt, name, monkeypatch):
    cfg, params = model
    how = FAULTS[name]
    monkeypatch.setattr(mla_moe, "_K_BLOCK", 16)
    if "project" in how:
        monkeypatch.setattr(mla_moe, "mla_project", how["project"])
    if "at_of" in how:
        key_block_reads(monkeypatch, 16, how["at_of"])
    served = serve(cfg.with_(**how.get("cfg", {})), how.get("params", lambda p: p)(params),
                   prompt, 6, chunks=(17, 17, 6))
    out = check(params, prompt, served)
    d = out["decoded"]
    worst = max(d["median_abs_diff"] / d["median_tolerance"],
                d["max_abs_diff"] / d["token_tolerance"],
                out["first"]["max_abs_diff"] / out["first"]["token_tolerance"]
                if "first" in out else 0.0)
    assert not out["ok"] and worst > 5, (name, out)
    print(f"\n{name}: decoded median {d['median_abs_diff']:.3f}, max {d['max_abs_diff']:.3f}")
