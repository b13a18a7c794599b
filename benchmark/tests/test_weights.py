"""Seeded weights: the tree the loader would build, from the seed alone,
and a substitution that fails loudly."""

import jax
import numpy as np
import pytest

from benchmark.lib import weights


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
