"""The plain reference against the program's own forward at a tiny Granite
size, and proof that the comparison is tight enough: a dropped multiplier or
a wrong kv-head grouping fails it."""

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


@pytest.fixture(scope="module")
def served():
    # at d 64, N(0, 0.02) blocks add nothing to a stream the x12 embedding
    # fills: draw at 1/sqrt(d) so that every block matters as it does at
    # the real widths (0.02 is 1.28/sqrt(4096))
    mp = pytest.MonkeyPatch()
    mp.setattr(weights, "INIT_STD", 0.125)
    try:
        yield _served()
    finally:
        mp.undo()


def _served():
    """The tree the engine would serve (seeded, int8) and the program's own
    logprobs for one prompt through models.llama.forward in bf16."""
    from nats_llm_studio_tpu.models.llama import forward, make_cache
    from nats_llm_studio_tpu.parallel.mesh import build_mesh

    cfg = REF.model_config(CONF, 128)
    mesh = build_mesh({"tp": 1}, devices=jax.local_devices()[:1])
    params = weights.make_seeded_params(1234)(None, cfg, mesh, quant="int8")
    toks = list(np.random.default_rng(0).integers(32, 127, size=48))
    k, v = make_cache(cfg, 1, 128)
    logits, _, _ = forward(params, cfg, jnp.asarray([toks], jnp.int32), k, v,
                           jnp.zeros((1,), jnp.int32))
    lp = np.asarray(jax.nn.log_softmax(logits[0, -1].astype(jnp.float32)))
    return params, toks, lp


# The chip's tolerances stand at about twice the noise measured there (bf16
# through 40 layers: largest difference up to 3.3, see lib/correct.py). The
# toy's noise is 0.1-0.3, so the same rule gives it these.
TOY = {"median_tol": 0.25, "token_tol": 0.6}


def entries(lp, k=8):
    return [{"token": chr(int(i)), "bytes": [int(i)], "logprob": float(lp[i])}
            for i in np.argsort(-lp)[:k]]


def test_reference_agrees_with_the_program(served):
    params, toks, lp = served
    out = correct.compare_all([(REF.last_logprobs(params, CONF, toks), entries(lp, 5))], **TOY)
    assert out["ok"], out
    assert out["max_abs_diff"] < 0.3     # bf16 activations through 3 tiny layers


@pytest.mark.parametrize("key,value", [
    ("residual_multiplier", 1.0), ("embedding_multiplier", 1.0),
    ("logits_scaling", 1.0), ("attention_multiplier", 0.0625)])
def test_a_dropped_multiplier_fails(served, key, value):
    params, toks, lp = served
    out = correct.compare_all([(REF.last_logprobs(params, dict(CONF, **{key: value}), toks), entries(lp, 5))], **TOY)
    assert not out["ok"], (key, out)


def test_a_wrong_kv_grouping_fails(served, monkeypatch):
    params, toks, lp = served
    monkeypatch.setattr(REF, "kv_head_of", lambda h, hq, hkv: h % hkv)
    out = correct.compare_all([(REF.last_logprobs(params, CONF, toks), entries(lp, 5))], **TOY)
    assert not out["ok"], out


def test_head_is_loud_on_printable_bytes(served):
    _, _, lp = served
    top = np.argsort(-lp)[:20]
    assert all(weights.ASCII_LO <= t < weights.ASCII_HI for t in top)
