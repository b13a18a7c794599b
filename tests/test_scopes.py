"""Names inside the jitted programs (obs/spans.py ``SCOPE_NAMES``): every
product, kernel and convolution of a family's decode burst and prefill chunk
lies under a scope of the vocabulary, a scope changes no operation, and every
program of a table has a kind (obs/roofline.py ``program_kind``). A refactor
that drops a scope fails here, not on the chip."""

import ast
import json
import re
from contextlib import nullcontext
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from nats_llm_studio_tpu.engine.sampling import sample_rows
from nats_llm_studio_tpu.models.config import ModelConfig
from nats_llm_studio_tpu.models.llama import family_module, init_params, make_cache
from nats_llm_studio_tpu.obs import roofline
from nats_llm_studio_tpu.obs.spans import SCOPE_NAMES, scope_of
from nats_llm_studio_tpu.ops.kvcache import WithState, kv_pool_zeros
from nats_llm_studio_tpu.serve.programs import build_programs

ROOT = Path(__file__).resolve().parent.parent
FAMILIES = {"dense": None, "latent": ("mla_moe_mhc", "tiny-mla"),
            "latent_plain": ("mla_moe_plain", "tiny-mla-plain"),
            "state": ("ssm_hybrid", "tiny-ssm"), "window": ("swa_gated_moe", "tiny-swa"),
            "linear": ("gdn_moe", "tiny-gdn"), "lightning": ("sala", "tiny-sala"),
            "latent_experts": ("ssm_latent_moe", "tiny-nemotron")}
SEQ, BLOCK, SLOTS, CHUNK, BURST = 64, 16, 2, 32, 2
SCOPED_FILES = ("models/llama.py", "models/mla_moe.py", "models/ssm_hybrid.py",
                "models/swa_moe.py", "models/gdn_moe.py", "models/sala.py", "models/experts.py",
                "serve/programs.py")
# what the acceptance counts: the operations that carry a step's time
HEAVY = re.compile(r"stablehlo\.(dot_general|custom_call|convolution)\b")
# a custom call that computes nothing: a sharding or layout annotation
ANNOTATION = re.compile(r"call_target_name = \"(Sharding|LayoutConstraint|annotate_device_placement)\"")


def _cfg(family: str, seq: int = SEQ) -> ModelConfig:
    if FAMILIES[family] is None:
        return ModelConfig.tiny(n_layers=2, max_seq_len=seq)
    from benchmark import run

    reference, toy = FAMILIES[family]
    ref = run.load_module(ROOT / f"benchmark/references/{reference}.py")
    conf = json.loads((ROOT / f"benchmark/tests/rehearsal/configs/{toy}.json").read_text())
    return ref.model_config(conf, seq).with_(dtype="float32")


def _table(cfg: ModelConfig) -> dict:
    return build_programs(cfg, None, max_seq=SEQ, paged=True, kv_block_tokens=BLOCK,
                          sample_rows=sample_rows)


def _pools(cfg: ModelConfig):
    """The block pool pair as the batcher makes it (``make_pool``)."""
    pair = [kv_pool_zeros((SLOTS * SEQ // BLOCK + 1, cfg.n_kv_layers, h, BLOCK, w), jnp.float32)
            for h, w in cfg.kv_cache_dims()]
    if cfg.slot_state:
        pair = [WithState(p, st, ax)
                for p, (st, ax) in zip(pair, family_module(cfg).make_state(cfg, SLOTS))]
    return pair


def _lowered(family: str, program: str) -> str:
    """The program's lowered text with its locations (``op_name`` paths)."""
    cfg = _cfg(family)
    table = _table(cfg)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    floats = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    if program == "decode":
        kp, vp = jax.eval_shape(lambda: _pools(cfg))
        low = table["decode_pallas"].lower(
            params, ints(SLOTS), kp, vp, ints(SLOTS, SEQ // BLOCK), ints(SLOTS), ints(SLOTS),
            ints(SLOTS), floats(SLOTS), ints(SLOTS), floats(SLOTS), BURST)
    else:
        km, vm = jax.eval_shape(lambda: make_cache(cfg, SLOTS, SEQ))
        low = table["prefill_chunk_group"].lower(
            params, ints(SLOTS, CHUNK), km, vm, ints(SLOTS), ints(SLOTS), SEQ)
    return low.as_text(debug_info=True)


def _op_names(text: str) -> dict[str, str]:
    """{``#locN``: the name it carries} of a lowered text; a location that
    wraps another (``loc("name"(#locM))``) carries its own name."""
    return dict(re.findall(r"^(#loc\d+) = loc\(\"([^\"]*)\"", text, flags=re.M))


def _named_operations(text: str) -> list[tuple[str, str]]:
    """(operation line, its ``op_name`` as XLA will join it) for every line of
    the lowered text that ends in a location. A function called from another
    (an inner ``jit``, a scan's ``closed_call``) names its operations from its
    own start: the path of its first call site goes in front, as XLA's
    inliner puts it."""
    names = _op_names(text)
    inside, callers, ops = None, {}, []
    for line in text.splitlines():
        m = re.match(r"\s*func\.func \w+ @([\w.]+)\(", line)
        if m:
            inside = m.group(1)
            continue
        loc = re.search(r"loc\((#loc\d+)\)\s*$", line)
        name = names.get(loc.group(1), "") if loc else ""
        call = re.search(r"\bcall @([\w.]+)\(", line)
        if call:
            callers.setdefault(call.group(1), (inside, name))
        ops.append((inside, line, name))

    def prefix(fn, depth=0):
        if fn not in callers or depth > 16:
            return ""
        caller, at = callers[fn]
        return f"{prefix(caller, depth + 1)}/{at}"

    return [(line, f"{prefix(fn)}/{name}") for fn, line, name in ops]


CASES = [(f, p) for f in FAMILIES for p in ("decode", "prefill")]


@pytest.mark.parametrize("family,program", CASES)
def test_every_product_and_kernel_lies_under_a_scope(family, program):
    ops = _named_operations(_lowered(family, program))
    heavy = [(l, n) for l, n in ops if HEAVY.search(l) and not ANNOTATION.search(l)]
    assert len(heavy) >= 6, "the toy program holds a layer's products"
    outside = [f"{l.strip()[:120]} :: {n}" for l, n in heavy if scope_of(n) not in SCOPE_NAMES]
    assert not outside, outside
    # both halves of a layer, the head and (decode) the sampling are named
    seen = {scope_of(n) for _, n in ops} - {None}
    assert {"embed", "head/logits"} <= seen, seen
    assert any(s.startswith("seq/") for s in seen) and any(s.startswith("ffn") for s in seen)
    if program == "decode":
        assert "head/sample" in seen
    if family == "latent":
        assert "mix" in seen and "seq/mla" in seen
    if family == "latent_plain":   # one stream: the same words, never the mixers'
        assert "mix" not in seen and {"seq/mla", "ffn/experts", "ffn/shared"} <= seen
    if family == "state":
        assert "seq/ssm" in seen and "seq/attn" in seen
    if family == "window":
        assert "seq/window" in seen and "ffn/experts" in seen
    if family == "linear":
        assert {"seq/linear", "seq/attn", "ffn/router", "ffn/experts", "ffn/shared"} <= seen
    if family == "latent_experts":   # one sublayer a layer: no dense MLP behind a mixer
        assert {"seq/ssm", "seq/attn", "ffn/router", "ffn/experts", "ffn/shared",
                "ffn/latent_down", "ffn/latent_up"} <= seen and "ffn/mlp" not in seen
    if family == "lightning":
        assert {"seq/linear", "seq/sparse", "seq/sparse/pool", "ffn/mlp"} <= seen
        # a decode step always scores and picks; a chunk of 32 into a context
        # of 64 can never pass the toy's dense length of 96, and holds no selection
        assert ("seq/sparse/select" in seen) == (program == "decode")


def _operations(text: str) -> list[str]:
    """The text's operations with what only names them taken out."""
    text = re.sub(r"\s*loc\((?:[^()]|\((?:[^()]|\([^()]*\))*\))*\)", "", text)
    return [l for l in text.splitlines() if l.strip() and not l.startswith("#loc")]


@pytest.mark.parametrize("family,program", CASES)
def test_a_scope_changes_no_operation(family, program, monkeypatch):
    named = _lowered(family, program)
    monkeypatch.setattr(jax, "named_scope", lambda name: nullcontext())
    bare = _lowered(family, program)
    assert not any(scope_of(n) for n in _op_names(bare).values()), "the patch took"
    assert _operations(named) == _operations(bare)


def test_the_scopes_the_code_opens_are_the_vocabulary():
    words = {w for s in SCOPE_NAMES for w in (s, *s.split("/")[1:])}

    def strings(arg):  # "a", ("a" if ... else "b") or TABLE[key] of the file's own table of literals
        if isinstance(arg, ast.IfExp):
            return strings(arg.body) | strings(arg.orelse)
        if isinstance(arg, ast.Subscript) and isinstance(arg.value, ast.Name):
            return tables.get(arg.value.id, set())
        return {arg.value} if isinstance(arg, ast.Constant) else set()

    opened = set()
    for rel in SCOPED_FILES:
        tree = ast.parse((ROOT / "nats_llm_studio_tpu" / rel).read_text())
        tables = {t.id: {v.value for v in node.value.values}
                  for node in tree.body if isinstance(node, ast.Assign)
                  and isinstance(node.value, ast.Dict)
                  and all(isinstance(v, ast.Constant) for v in node.value.values)
                  for t in node.targets if isinstance(t, ast.Name)}
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)):
                continue
            if node.func.attr == "named_scope":
                assert strings(node.args[0]), ast.unparse(node)  # a literal, so that grep finds it
                opened |= strings(node.args[0])
    assert opened and opened <= words, opened - words
    assert scope_of("jit(decode_pos_moe)/while/body/closed_call/ffn/router/dot_general") \
        == "ffn/router"
    assert scope_of("jit(f)/while/body/seq/attn/vmap()/dynamic_update_slice") == "seq/attn"
    assert scope_of("jit(f)/seq/transpose") == "seq"
    assert scope_of("jit(prefill1)/while/body/closed_call") is None


@pytest.mark.parametrize("family", list(FAMILIES))
def test_every_program_of_a_table_has_a_kind(family):
    table = _table(_cfg(family))
    for name, fn in table.items():
        kind = roofline.program_kind(fn.__name__)
        # the name a trace gives says what the table's name says
        assert kind == roofline.table_kind(name), (name, fn.__name__)
        if name in roofline.SPEC_PROGRAMS:
            assert kind == "spec" and roofline.classify_program(name) == "decode"
        elif name in roofline.PREFILL_PROGRAMS | roofline.DECODE_PROGRAMS:
            assert kind == roofline.classify_program(name), name
        else:  # pool and ring copies: nothing of a request's prefill or decode
            assert kind == "other", name
    # every forward-bearing program is a prefill, a decode or a verify
    for name in ("prefill1", "prefill_chunk_group", "admit_many_fused_paged",
                 "finish_admit_group_paged", "decode_pallas", "decode_pallas_ext"):
        assert roofline.program_kind(table[name].__name__) in ("prefill", "decode"), name
    assert roofline.program_kind("no_such_program") == "other"
    roofline.note_programs(table)  # what the worker's page lists
    assert roofline.program_kinds()[table["decode_pallas"].__name__] == "decode"
