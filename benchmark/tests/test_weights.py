"""Seeded weights: the tree the loader would build, from the seed alone,
and a substitution that fails loudly."""

import hashlib
import json
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from benchmark.lib import weights

# every leaf of a tiny dense and a tiny MoE tree as the builder of PR 23-26
# made it (a fixed list of leaves), recorded before the builder took its
# schema from the program: today's cells serve bit for bit the same weights
GOLDEN = json.loads((Path(__file__).parent / "golden_weights.json").read_text())


def tiny_cfg():
    from nats_llm_studio_tpu.models.config import ModelConfig

    return ModelConfig.tiny(n_layers=2, vocab_size=300, arch="granite", logit_scale=0.5)


def mesh1():
    from nats_llm_studio_tpu.parallel.mesh import build_mesh

    return build_mesh({"tp": 1}, devices=jax.local_devices()[:1])


def test_same_schema_as_the_programs_own_init():
    from nats_llm_studio_tpu.models.llama import ensure_lm_head, init_params

    cfg = tiny_cfg()
    want = jax.eval_shape(lambda: ensure_lm_head(init_params(cfg, jax.random.PRNGKey(0))))
    got = weights.make_seeded_params(7)(None, cfg, mesh1(), quant="none")
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert all(a.shape == b.shape and a.dtype == b.dtype
               for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)))


def test_seed_decides_the_weights_and_large_seeds_work():
    cfg, mesh = tiny_cfg(), mesh1()
    build = weights.make_seeded_params(2**31 + 11)
    a = build(None, cfg, mesh, quant="int8")
    b = weights.make_seeded_params(2**31 + 11)(None, cfg, mesh, quant="int8")
    c = weights.make_seeded_params(11)(None, cfg, mesh, quant="int8")
    eq = lambda x, y: all(np.array_equal(p, q) for p, q in zip(jax.tree.leaves(x), jax.tree.leaves(y)))
    assert eq(a, b) and not eq(a, c)
    assert a["blocks"]["wq"].q.dtype == np.int8 and a["blocks"]["wq"].s.shape[-2] == 1
    assert build.last_build["bytes"] > 0


def test_install_fails_loudly(monkeypatch):
    from nats_llm_studio_tpu.parallel import loader

    monkeypatch.setattr(loader, "load_params_sharded", lambda reader, cfg, mesh: None)
    with pytest.raises(RuntimeError, match="no longer matches"):
        weights.install(1)
    monkeypatch.delattr(loader, "load_params_sharded")
    with pytest.raises(RuntimeError, match="is gone"):
        weights.install(1)


def test_install_substitutes_exactly_one_name(monkeypatch):
    from nats_llm_studio_tpu.parallel import loader

    before = dict(vars(loader))
    monkeypatch.setattr(loader, "load_params_sharded", loader.load_params_sharded)
    weights.install(5)
    changed = [k for k, v in vars(loader).items() if before.get(k) is not v]
    assert changed == ["load_params_sharded"]


def digests(tree) -> dict:
    out = {}
    for path, leaf in sorted(weights.flatten(tree).items()):
        parts = {".q": leaf.q, ".s": leaf.s} if hasattr(leaf, "q") else {"": leaf}
        for suffix, x in parts.items():
            a = np.asarray(x)
            out[path + suffix] = hashlib.sha256(
                str(a.dtype).encode() + str(a.shape).encode() + a.tobytes()).hexdigest()[:16]
    return out


@pytest.mark.parametrize("case", sorted(GOLDEN["digests"]))
def test_todays_trees_are_bit_identical_to_the_first_builders(case):
    from nats_llm_studio_tpu.models.config import ModelConfig

    family, quant, seed = case.split("/")
    cfg = ModelConfig.tiny(**GOLDEN["configs"][family])
    tree = weights.make_seeded_params(int(seed))(None, cfg, mesh1(), quant=quant)
    assert digests(tree) == GOLDEN["digests"][case]


def new_family_shapes(cfg):
    """A schema with leaves the first builder's list never had: a [L, d, hkv]
    projection, a [L, hd] norm gain, a bias, a second expert stack."""
    L, d, hd = cfg.n_layers, cfg.d_model, cfg.head_dim
    tree = weights.program_param_shapes(cfg)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    tree["blocks"] |= {"w_g": f32(L, d, cfg.n_kv_heads), "q_norm": f32(L, hd),
                       "b_g": f32(L, cfg.n_kv_heads), "w_up_s": f32(L, 2, d, cfg.d_ff)}
    tree["gate_scale"] = f32(d)
    return tree


@pytest.fixture
def new_family_rules(monkeypatch):
    from nats_llm_studio_tpu.ops import wquant
    from nats_llm_studio_tpu.parallel import sharding

    plain = sharding.param_sharding_rules
    extra = {"blocks.w_g": P(None, None, None), "blocks.q_norm": P(None, None),
             "blocks.b_g": P(None, None), "blocks.w_up_s": P(None, None, None, None),
             "gate_scale": P(None)}
    monkeypatch.setattr(sharding, "param_sharding_rules",
                        lambda mesh, cfg=None: plain(mesh, cfg) | extra)
    monkeypatch.setattr(wquant, "_QUANT_KEYS", wquant._QUANT_KEYS | {"w_up_s"})
    return extra


def test_a_schema_with_new_leaves_is_built_by_the_rules_alone(new_family_rules):
    cfg, mesh = tiny_cfg().with_(dtype="bfloat16"), mesh1()
    tree = weights.make_seeded_params(3, SimpleNamespace(param_shapes=new_family_shapes))(
        None, cfg, mesh, quant="int8")
    b = tree["blocks"]
    L, d = cfg.n_layers, cfg.d_model
    assert b["w_g"].shape == (L, d, cfg.n_kv_heads) and b["w_g"].dtype == jnp.bfloat16
    assert np.all(np.asarray(b["q_norm"], np.float32) == 1.0)          # rank-1 ...norm: ones
    assert 0.01 < float(np.std(np.asarray(b["b_g"], np.float32))) < 0.04  # a bias is a leaf like any other
    assert 0.015 < float(np.std(np.asarray(tree["gate_scale"], np.float32))) < 0.025
    assert b["w_up_s"].q.dtype == np.int8 and b["w_up_s"].s.shape == (L, 2, 1, cfg.d_ff)
    assert b["w_up_s"].q.sharding.spec == new_family_rules["blocks.w_up_s"]
    # new leaves draw from their own streams, and layers differ
    assert not np.array_equal(np.asarray(b["w_g"][0]), np.asarray(b["w_g"][1]))
    # and the leaves the first builder knew are what they were
    plain = weights.make_seeded_params(3)(None, cfg, mesh, quant="int8")
    assert np.array_equal(np.asarray(plain["blocks"]["wq"].q), np.asarray(b["wq"].q))
    assert np.array_equal(np.asarray(plain["embed"]), np.asarray(tree["embed"]))


def test_a_leaf_without_a_sharding_rule_raises_by_name():
    with pytest.raises(KeyError, match="blocks.w_g"):
        weights.make_seeded_params(3, SimpleNamespace(param_shapes=new_family_shapes))(
            None, tiny_cfg(), mesh1(), quant="none")


def test_the_schema_fails_loudly_when_the_programs_initialiser_is_gone(monkeypatch):
    from nats_llm_studio_tpu.models import llama

    monkeypatch.delattr(llama, "init_params")
    with pytest.raises(RuntimeError, match="init_params"):
        weights.make_seeded_params(3)(None, tiny_cfg(), mesh1())


def test_a_bias_family_is_built_where_the_first_builder_refused():
    cfg = tiny_cfg().with_(attn_bias=True)
    tree = weights.make_seeded_params(3)(None, cfg, mesh1(), quant="int8")
    hq = cfg.n_heads * cfg.head_dim
    assert tree["blocks"]["bq"].shape == (cfg.n_layers, hq)
    assert float(np.std(np.asarray(tree["blocks"]["bq"], np.float32))) > 0.01


def test_a_familys_gains_are_applied_and_an_unknown_leaf_raises():
    cfg, mesh = tiny_cfg(), mesh1()
    std = lambda x: float(np.std(np.asarray(x, np.float32)))
    plain = weights.make_seeded_params(3)(None, cfg, mesh)
    loud = weights.make_seeded_params(3, SimpleNamespace(weight_gains={"wv": 3.0, "blocks.wq": 1.0}))(None, cfg, mesh)
    assert std(loud["blocks"]["wv"]) == pytest.approx(3.0 * std(plain["blocks"]["wv"]), rel=1e-3)
    # a key overrides the default gain of wq; wk keeps QK_GAIN
    assert std(loud["blocks"]["wq"]) == pytest.approx(std(plain["blocks"]["wq"]) / weights.QK_GAIN, rel=1e-3)
    assert np.array_equal(np.asarray(loud["blocks"]["wk"]), np.asarray(plain["blocks"]["wk"]))
    with pytest.raises(ValueError, match="w_gates"):
        weights.make_seeded_params(3, SimpleNamespace(weight_gains={"w_gates": 2.0}))(None, cfg, mesh)


def test_install_passes_the_schema_and_the_gains_on(monkeypatch):
    from nats_llm_studio_tpu.parallel import loader

    monkeypatch.setattr(loader, "load_params_sharded", loader.load_params_sharded)
    build = weights.install(5, SimpleNamespace(param_shapes=weights.program_param_shapes,
                                               weight_gains={"wo": 2.0}))
    cfg, mesh = tiny_cfg(), mesh1()
    plain = weights.make_seeded_params(5)(None, cfg, mesh)
    assert float(np.std(np.asarray(build(None, cfg, mesh)["blocks"]["wo"]))) == pytest.approx(
        2.0 * float(np.std(np.asarray(plain["blocks"]["wo"]))), rel=1e-3)


def test_a_leaf_the_configuration_pins_is_the_same_for_every_seed():
    """``fixed_draws``: a leaf that decides how much work a step is (a router,
    its selection bias) is drawn from the configuration's number, every other
    leaf from the seed; a pinned leaf is what some seed would have drawn."""
    cfg, mesh = tiny_cfg(), mesh1()
    arr = lambda x: np.asarray(x, np.float32)
    pins = {"wo": 29, "blocks.w_up": 29}
    a = weights.make_seeded_params(3, fixed_draws=pins)(None, cfg, mesh)
    b = weights.make_seeded_params(2**31 + 11, fixed_draws=pins)(None, cfg, mesh)
    plain = weights.make_seeded_params(3)(None, cfg, mesh)
    for name in ("wo", "w_up"):
        assert np.array_equal(arr(a["blocks"][name]), arr(b["blocks"][name]))
        assert not np.array_equal(arr(a["blocks"][name]), arr(plain["blocks"][name]))
        # one draw of the same distribution: the seed 29 would have made it
        assert np.array_equal(arr(a["blocks"][name]), arr(
            weights.make_seeded_params(29)(None, cfg, mesh)["blocks"][name]))
    for name in ("wq", "wv", "w_down"):
        assert np.array_equal(arr(a["blocks"][name]), arr(plain["blocks"][name]))
        assert not np.array_equal(arr(a["blocks"][name]), arr(b["blocks"][name]))
    assert np.array_equal(arr(a["embed"]), arr(plain["embed"]))
    with pytest.raises(ValueError, match="fixed_draws names 'router'"):
        weights.make_seeded_params(3, fixed_draws={"router": 29})(None, cfg, mesh)


def test_the_one_configuration_that_pins_leaves_pins_its_routing():
    confs = {p.stem: json.loads(p.read_text())
             for p in (Path(weights.__file__).parent.parent / "configs").glob("*.json")}
    assert {k: v["fixed_draws"] for k, v in confs.items() if "fixed_draws" in v} == {
        "xing4.0-29b-a4b": {"router": 29, "e_bias": 29}}
