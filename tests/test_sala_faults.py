"""Faults put into the lightning / block-sparse family on purpose: each must
fail the toy limits of ``tests/test_sala.py`` by a wide margin, through a
chunked prefill past the dense length and eight paged decode steps against the
plain reference. A file of its own so that ``--dist loadfile`` gives it a
worker of its own."""

import jax.numpy as jnp
import pytest
from test_sala import check, model, serve, tokens  # noqa: F401 — fixtures

from nats_llm_studio_tpu.models import sala
from nats_llm_studio_tpu.ops import lightning


def _one_head_selects_for_its_group(monkeypatch):
    sound = sala.block_scores

    def first_head(q, pooled, n, cfg):
        g = cfg.n_heads // cfg.n_kv_heads
        return sound(jnp.repeat(q[:, :, ::g], g, axis=2), pooled, n, cfg)

    monkeypatch.setattr(sala, "block_scores", first_head)


def _pooled_key_read_once_its_first_key_exists(monkeypatch):
    def early(n, count, cfg):
        return jnp.arange(count, dtype=jnp.int32) * cfg.sparse_stride < n[..., None]

    monkeypatch.setattr(sala, "pooled_exist", early)


def _padding_decays_the_state(monkeypatch):
    sound = lightning.lightning_chunked

    def through(q, k, v, a, valid, s0, chunk=lightning.CHUNK):
        real = (jnp.arange(q.shape[1])[None, :] < valid[:, None])[..., None, None]
        return sound(q, jnp.where(real, k, 0), jnp.where(real, v, 0), a,
                     jnp.full_like(valid, q.shape[1]), s0, chunk)

    monkeypatch.setattr(lightning, "lightning_chunked", through)


def _gate_on_the_wrong_half(params):
    attn = dict(params["blocks"]["attn"])
    half = attn["wq"].shape[-1] // 2
    attn["wq"] = jnp.concatenate([attn["wq"][..., half:], attn["wq"][..., :half]], axis=-1)
    return dict(params, blocks=dict(params["blocks"], attn=attn))


def _no_rotary_in_the_lightning_layers(monkeypatch):
    monkeypatch.setattr(sala, "apply_rope", lambda x, cos, sin: x)


FAULTS = {
    "the window not forced": dict(cfg=dict(sparse_window=0)),
    "the initial block not forced": dict(cfg=dict(sparse_init_blocks=0)),
    "selection by one query head instead of the group": dict(
        patch=_one_head_selects_for_its_group),
    "a pooled key read before its last key exists": dict(
        patch=_pooled_key_read_once_its_first_key_exists),
    "the decay advanced over a chunk's padding": dict(
        patch=_padding_decays_the_state, how=dict(chunks=(100, 100, 30), pad=20)),
    "plain attention kept past the dense length": dict(cfg=dict(sparse_dense_len=256)),
    "the output gate on the wrong half of wq": dict(params=_gate_on_the_wrong_half),
    "no rotary in the lightning layers": dict(patch=_no_rotary_in_the_lightning_layers),
    "the residual scale of the cut's depth, not the published one": dict(
        cfg=dict(residual_scale=1.4 / 6 ** 0.5)),
}


@pytest.mark.parametrize("name", list(FAULTS))
def test_a_fault_put_in_on_purpose_fails_the_toy_limits(model, name, monkeypatch):  # noqa: F811
    cfg, params = model
    how = FAULTS[name]
    if "patch" in how:
        how["patch"](monkeypatch)
    prompt = tokens(1, 230)
    served, _ = serve(cfg.with_(**how.get("cfg", {})), how.get("params", lambda p: p)(params),
                      prompt, 9, **how.get("how", dict(chunks=(100, 100, 30))))
    monkeypatch.undo()
    out = check(params, prompt, served)
    d = out["decoded"]
    worst = max(out["max_abs_diff"], d["max_abs_diff"])
    assert not out["ok"], (name, out)
    assert worst > 0.3, (name, worst)   # the sound path reads under 2e-3


def test_the_sound_path_passes_the_same_prompt_and_chunks(model):  # noqa: F811
    cfg, params = model
    prompt = tokens(1, 230)
    served, _ = serve(cfg, params, prompt, 9, chunks=(100, 100, 30), pad=20)
    out = check(params, prompt, served)
    assert out["ok"] and max(out["max_abs_diff"], out["decoded"]["max_abs_diff"]) < 2e-3, out
