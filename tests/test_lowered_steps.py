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
a layer and their digests are PR 52's (the parent's are in ``CHANGES.md``)."""
import hashlib
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from benchmark import run
from nats_llm_studio_tpu.models import llama
from nats_llm_studio_tpu.ops.kvcache import WithState

ROOT = Path(__file__).resolve().parents[1]
T, SEQ, B = 16, 128, 2
# the toys' references by name: the benchmark's own, then the rehearsal's
REHEARSAL = run.Manifest(ROOT / "benchmark/tests/rehearsal/manifest.json")
LOWERED_SHA = {
    ("tiny-ssm", "decode"): "1319c28ec4b315cd599c857829fd3d1347ca5fee94174f711fef83e05eb63d99",
    ("tiny-ssm", "prefill"): "dbdb7b5b22920b406eabb79a1e9db38bbf9d40d196d0cd7d7718b7387d9f763a",
    ("tiny-swa", "decode"): "545103ca9ea56b7e38c69146209d316d4dc6242520fd8733966d676aefe4759c",
    ("tiny-swa", "decode_counted"): "caabf44f6d5504437f784bd32e2a93b5634aa0234fe4c05900dcb4bb2a6aa7f3",
    ("tiny-swa", "prefill"): "e43b687298415726f978fdab68b742b5aa2685e69ad467d8c0c1ad5c02b9f7cc",
    ("tiny-mla-plain", "decode"): "54c9c74669ea7d89983ecef38ee75159e0e08e338abed56179a0e77b1bbdf034",
    ("tiny-mla-plain", "decode_counted"): "d9db576855330906ca3878524a3348ddaad25ef992f237f45c308b2bae2ab74f",
    ("tiny-mla-plain", "prefill"): "2a83d2fd7d0f477154a85b0a3095a703d8e832a57ae9b1f8f9d806354b2adb09",
    ("tiny-mla", "decode"): "9149ebb86ea6b54e1325400ba4802369f6a72bea8b8d30c5f4b757c4f2996082",
    ("tiny-mla", "decode_counted"): "9147ef682c95e5fecb95ad433915bfeba2a0f3380597d878c50c6f2a0685bba5",
    ("tiny-mla", "prefill"): "6c33a1196acfc7ee8ffbea0d7919a0a7d80bb61eca02ac3d9b196deb8fa0a876",
    ("tiny-gdn", "decode"): "656aa831da43c3e695c8f31a862f123b134ff72981bb1afe5b755b6161d5db0c",
    ("tiny-gdn", "decode_counted"): "b2983607e1b53c43ee7e25a3c26e406b3863a398605d1703dd606ecacbb06789",
    ("tiny-gdn", "prefill"): "220c1cbba1892b16668bf2f5a4d24adf009723a618f80df307203854913ff653",
    ("tiny-sala", "decode"): "623f40cdc1ce6eb86c131782e7d9214409841a04388cb734dbc3ad4eee06ddea",
    ("tiny-sala", "prefill"): "f146f6fa3e995900ee5641ce71b0e48ec4ab45f73bd18e8ec1f419ce03ff59aa",
    ("tiny-granite", "decode"): "4d3a0abc45bc4b9d95e5d2ca7eaba9822dacf8f5f632a44883d9e2a154649e17",
    ("tiny-granite", "prefill"): "4fad5d90689e3b2428d669cd5fbfc29370f0a76ea11f1cde122fccf5907a83d8",
    ("tiny-bias", "decode"): "fbf069912e6e976a88066bf91e754088b5849234f493369ab3f97df205094285",
    ("tiny-bias", "prefill"): "68c6e487c621d9c3a7cdc731ad36f0f4be66871daf0b5ae3b6e4b154566a53a7",
}


def _operations(text: str) -> list[str]:
    text = re.sub(r"\s*loc\((?:[^()]|\((?:[^()]|\([^()]*\))*\))*\)", "", text)
    return [l for l in text.splitlines() if l.strip() and not l.startswith("#loc")]


def _ints(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _lowered(toy: str, program: str) -> str:
    conf = json.loads((ROOT / f"benchmark/tests/rehearsal/configs/{toy}.json").read_text())
    ref = run.load_module(REHEARSAL.find("references", conf["reference"], (".py",)))
    cfg = ref.model_config(conf, SEQ).with_(dtype="float32")
    params = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    if program == "prefill":
        caches = jax.eval_shape(lambda: llama.make_cache(cfg, 2, SEQ))
        return jax.jit(lambda p, tok, kc, vc, pos, lp: llama.forward(
            p, cfg, tok, kc, vc, pos, logit_positions=lp)).lower(
                params, _ints(2, 32), *caches, _ints(2), _ints(2)).as_text()
    pools = [jax.ShapeDtypeStruct((9, cfg.n_kv_layers, h, T, w), jnp.float32)
             for h, w in cfg.kv_cache_dims()]
    if cfg.slot_state:
        state = jax.eval_shape(lambda: llama.family_module(cfg).make_state(cfg, B))
        pools = [WithState(p, s, axes) for p, (s, axes) in zip(pools, state)]
    return jax.jit(lambda p, tok, kp, vp, tbl, pos: llama.forward_decode_paged(
        p, cfg, tok, kp, vp, tbl, pos, moe_stats=program == "decode_counted")).lower(
            params, _ints(B, 1), *pools, _ints(B, SEQ // T), _ints(B)).as_text()


@pytest.mark.parametrize("toy,program", list(LOWERED_SHA), ids=lambda v: v)
def test_an_earlier_familys_lowered_program_is_what_it_was(toy, program):
    ops = _operations(_lowered(toy, program))
    assert hashlib.sha256("\n".join(ops).encode()).hexdigest() == LOWERED_SHA[toy, program]
