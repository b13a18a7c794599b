"""The lowered programs of the families that were there before the
linear-attention family came in are, operation for operation, what they were.

``models/ssm_hybrid.py _layers`` takes its mixers by kind and an FFN half from
its caller, and ``models/experts.py`` routes by a softmax, holds a share of the
experts and gates the shared expert: code that ``tiny-ssm``, ``tiny-swa``,
``tiny-mla`` and ``tiny-mla-plain`` run too. Each digest below is the sha256 of
the operations (locations taken out) of a toy's paged decode step, of the same
step with the expert counters, and of a two-row prefill chunk, as the parent of
PR 47 lowered them and as the tree lowers them since. A deliberate change to
one of these paths records its new digest here and says so; a change made for
another family must never move one (``tests/test_mla_moe_plain.py`` holds the
first of them the same way). PR 49 recorded the four decode digests of
``tiny-mla`` and ``tiny-mla-plain`` anew: the latent decode kernel's walk
changed (``ops/mla_attention.py``: its own run length, the live list, a tail
that copies live blocks only), and the interpreter lowers the kernel's body
into the step. Their prefill digests did not move.

PR 52 put the last two families in (``tiny-gdn``, ``tiny-sala``: the parent's
digests, which its change to the dense family's file did not move) and the
dense family itself, ``tiny-granite`` and ``tiny-bias`` (q / k / v biases):
``models/llama.py _qkv_rows`` holds the three products apart from the split
into heads, so the dense step and chunk gained one ``optimization_barrier``
a layer and their digests are PR 52's (the parent's are in ``CHANGES.md``).

PR 53 wrote that barrier once (``ops/wquant.py flat_rows``) and put it between
the flat q / k / v products and the cut into heads of every other family:
the sixteen digests of ``tiny-ssm``, ``tiny-swa``, ``tiny-mla-plain``,
``tiny-mla``, ``tiny-gdn`` and ``tiny-sala`` are PR 53's (each program gained
one ``optimization_barrier`` a kind of layer that projects q / k / v; the
parent's are in ``CHANGES.md``), the four of the dense family did not move,
and ``test_holding_the_products_apart_changes_no_value`` runs every moved
program with the barrier and without it.

PR 57 laid the state-space family's convolution tails a tap a plane
([Lm, K, rows, C], as ``tiny-gdn``'s lay since PR 48) and gave
``ops/ssm_scan.py conv_step`` / ``causal_conv`` the tail as [K, B, C]: the
digests of ``tiny-ssm`` (decode, prefill) and of ``tiny-gdn``'s prefill (its
two ``swapaxes`` a linear layer are gone) are PR 57's, and ``tiny-nemotron``
(the same family at two groups with layers of experts alone) came in with its
three; the parent's are in ``CHANGES.md``. ``tiny-gdn``'s decode steps (the
Pallas prologue), ``tiny-sala`` (its K state is pooled keys, rows on axis 1)
and the dense, window and latent families did not move.
``tests/test_ssm_hybrid.py`` holds the moved programs' VALUES to the parent's
(``test_a_served_stream_is_the_stream_the_parent_commit_served``)."""
import hashlib
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run
from nats_llm_studio_tpu.models import llama
from nats_llm_studio_tpu.ops.kvcache import WithState

ROOT = Path(__file__).resolve().parents[1]
T, SEQ, B = 16, 128, 2
# the toys' references by name: the benchmark's own, then the rehearsal's
REHEARSAL = run.Manifest(ROOT / "benchmark/tests/rehearsal/manifest.json")
LOWERED_SHA = {
    ("tiny-ssm", "decode"): "b40678e4cfc2450d28dc4f67794da99ba094d9e6b3743ef53504fc9ae818bdb0",
    ("tiny-ssm", "prefill"): "01b06b666ffc2755d8ea88602aae486da030c92c5423020257eeda9ab3ed76ee",
    ("tiny-swa", "decode"): "693c8e351f5caa379d41cf9372395060b02666c04527753014801572318b4aa8",
    ("tiny-swa", "decode_counted"): "15a440d80007c6fd1a517cd524438c85d27a8a5988e52367394cd13de3fe0635",
    ("tiny-swa", "prefill"): "e6636ec1d835c54ed1e556bd37a1ae85cf89f0503ec82d6313a5a6ad2e1cacab",
    ("tiny-mla-plain", "decode"): "96ee0ca16c46204fad63a1fbfe3b7f459304d67a6f535b61c7a3b2cadb4f5a91",
    ("tiny-mla-plain", "decode_counted"): "e2b599439899c46961297813a2502ed3278f070fd0077311299ddcd122bf436a",
    ("tiny-mla-plain", "prefill"): "e7a33dbddb7926498d1079ee72fc0ba7765c4f8d9e82e46549472f51c65fc783",
    ("tiny-mla", "decode"): "8b1713151898696af571536a0790e71dcbc88d6c27c5b1edd32729d6b6d71d2b",
    ("tiny-mla", "decode_counted"): "b850c6396a49d51510166e6343c54b7eb7317422d77c5afcc8454785f3158fca",
    ("tiny-mla", "prefill"): "374776daf2483687dda6a92831f62d86a52edb8ef63af19c7fe70b728b3a6daa",
    ("tiny-gdn", "decode"): "4311d3242d157f2dfc781f3bc38f71091d25489e96ab3e96e4ea48ad05bdfe1e",
    ("tiny-gdn", "decode_counted"): "b1fe07b14d238b80cba7e787fff0c51ac7eaa044c6881634da153e15a45abb61",
    ("tiny-gdn", "prefill"): "2abedf16cd907235189db54096170f8d4df1aa57cc615d59e26d6e16bdef2326",
    ("tiny-sala", "decode"): "fbcb55dc371bdbf6422c04f608feaa95b92715fe4f39692465244288688aeb83",
    ("tiny-sala", "prefill"): "f80b9b2ca2eeef6e0625c11d429679520cc4d21b9537924828919f2c47f47d61",
    ("tiny-granite", "decode"): "4d3a0abc45bc4b9d95e5d2ca7eaba9822dacf8f5f632a44883d9e2a154649e17",
    ("tiny-granite", "prefill"): "4fad5d90689e3b2428d669cd5fbfc29370f0a76ea11f1cde122fccf5907a83d8",
    ("tiny-bias", "decode"): "fbf069912e6e976a88066bf91e754088b5849234f493369ab3f97df205094285",
    ("tiny-bias", "prefill"): "68c6e487c621d9c3a7cdc731ad36f0f4be66871daf0b5ae3b6e4b154566a53a7",
    ("tiny-nemotron", "decode"): "fba9e9b5ee93680a3016092dd53c0cf42bb34f90697eb68dfacd08a39cc9f16f",
    ("tiny-nemotron", "decode_counted"): "72f2d743918ec97b1bc45a38d4392d943c09f63fbe49774c07262e8ccdac343e",
    ("tiny-nemotron", "prefill"): "c9f8561499ad5690b3acded0209edb878075dca092fa30b6ae7c0860eafaed40",
}


def _operations(text: str) -> list[str]:
    text = re.sub(r"\s*loc\((?:[^()]|\((?:[^()]|\([^()]*\))*\))*\)", "", text)
    return [l for l in text.splitlines() if l.strip() and not l.startswith("#loc")]


def _ints(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _program(toy: str, program: str):
    """(cfg, the function of (params, *arguments), the arguments' shapes) of
    a toy's two-row prefill chunk or paged decode step in float32."""
    conf = json.loads((ROOT / f"benchmark/tests/rehearsal/configs/{toy}.json").read_text())
    ref = run.load_module(REHEARSAL.find("references", conf["reference"], (".py",)))
    cfg = ref.model_config(conf, SEQ).with_(dtype="float32")
    if program == "prefill":
        caches = jax.eval_shape(lambda: llama.make_cache(cfg, 2, SEQ))
        return cfg, (lambda p, tok, kc, vc, pos, lp: llama.forward(
            p, cfg, tok, kc, vc, pos, logit_positions=lp)), (_ints(2, 32), *caches, _ints(2), _ints(2))
    pools = [jax.ShapeDtypeStruct((9, cfg.n_kv_layers, h, T, w), jnp.float32)
             for h, w in cfg.kv_cache_dims()]
    if cfg.slot_state:
        state = jax.eval_shape(lambda: llama.family_module(cfg).make_state(cfg, B))
        pools = [WithState(p, s, axes) for p, (s, axes) in zip(pools, state)]
    return cfg, (lambda p, tok, kp, vp, tbl, pos: llama.forward_decode_paged(
        p, cfg, tok, kp, vp, tbl, pos, moe_stats=program == "decode_counted")), (
            _ints(B, 1), *pools, _ints(B, SEQ // T), _ints(B))


def _lowered(toy: str, program: str) -> str:
    cfg, fn, args = _program(toy, program)
    params = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    return jax.jit(fn).lower(params, *args).as_text()


@pytest.mark.parametrize("toy,program", list(LOWERED_SHA), ids=lambda v: v)
def test_an_earlier_familys_lowered_program_is_what_it_was(toy, program):
    ops = _operations(_lowered(toy, program))
    assert hashlib.sha256("\n".join(ops).encode()).hexdigest() == LOWERED_SHA[toy, program]


@pytest.mark.parametrize("toy,program", [
    (toy, program) for toy, program in LOWERED_SHA
    if toy not in ("tiny-granite", "tiny-bias") and program != "decode_counted"], ids=lambda v: v)
def test_holding_the_products_apart_changes_no_value(monkeypatch, toy, program):
    """``flat_rows`` is an ``optimization_barrier`` and nothing else: each
    family's chunk and decode step over drawn weights, tokens and pools give,
    to the last bit in float32, the logits, caches and states of the same
    program traced with the barrier taken out (the parent of PR 53's products;
    ``tests/test_models.py`` holds the dense family the same way)."""
    cfg, fn, shapes = _program(toy, program)
    params = jax.jit(lambda: llama.init_params(cfg, jax.random.PRNGKey(5)))()
    rng = np.random.default_rng(0)

    def drawn(x):
        if x.dtype != jnp.int32:
            return jnp.asarray(0.5 * rng.standard_normal(x.shape), x.dtype)
        return jnp.zeros(x.shape, jnp.int32)   # a state's row counts: nothing seen yet

    tokens, *held, a, b = jax.tree.map(drawn, shapes)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, tokens.shape), jnp.int32)
    if program == "prefill":   # two fresh rows, the logits of their last positions
        a, b = jnp.zeros(a.shape, jnp.int32), jnp.full(b.shape, tokens.shape[1] - 1, jnp.int32)
    else:                      # block tables of 4 blocks a row, positions inside them
        a = jnp.asarray([[1, 2, 3, 4, 0, 0, 0, 0], [5, 6, 7, 8, 0, 0, 0, 0]], jnp.int32)
        b = jnp.asarray([20, 37], jnp.int32)

    def run():  # a new function a call: traced anew
        return jax.jit(lambda *args: fn(*args))(params, tokens, *held, a, b)

    with_barrier = run()
    passed = []
    monkeypatch.setattr(jax.lax, "optimization_barrier", lambda x: passed.append(x) or x)
    without = run()
    assert passed   # the barrier was in the trace, and is out of this one
    assert np.isfinite(np.asarray(with_barrier[0])).all()   # logits, not NaN against NaN
    for x, y in zip(jax.tree.leaves(with_barrier), jax.tree.leaves(without)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
