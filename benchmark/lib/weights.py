"""Seeded weights made on the device, and the one program name the benchmark
overrides to serve them.

``LocalRegistry._load`` looks ``parallel.loader.load_params_sharded`` up at
call time; ``install(seed)`` sets that module attribute to ``seeded_params``,
which builds the same tree the loader would (stacked ``blocks``, ``embed``,
``out_norm``, ``lm_head``; int8 ``QTensor`` leaves under ``quant="int8"``)
in ONE jitted call from the seed, each leaf at the sharding
``parallel.sharding.param_sharding_rules`` gives it. Nothing touches the
host and no GGUF tensor is read: the models dir holds a header-only file.

The substitution fails loudly. If the attribute is gone or its signature
changed, ``install`` raises; there is no fallback to a GGUF load.

Copied, not imported: the leaf schema of ``bench.py:101-157``
(``init_params_int8``) and the printable-ASCII-loud head of
``chip_smoke.py:112-117``.
"""

from __future__ import annotations

import inspect
import time

# every N(0, INIT_STD) like models.llama.init_params
INIT_STD = 0.02
# the head's printable-ASCII columns (token id == byte value under the
# byte-level tokenizer) are scaled so that their logits have about this
# standard deviation AFTER the configuration's logit scale: chip_smoke.py's
# 8x at d 4096 with no logit scale is 0.02 * 64 * 8 = 10.2. The random model
# then emits printable bytes like a trained byte-level model: streams deliver
# chunk by chunk, and the eos id is practically never sampled (its logit is
# ~0 against a printable maximum of ~24), so every reply runs to max_tokens.
ASCII_LOGIT_STD = 10.0
# wq and wk are drawn this much louder. At N(0, 0.02) the attention scores of
# these widths have a spread of 0.1-0.15, every softmax is flat, attention
# returns the mean of the values and adds almost nothing to the stream: a
# wrong kv head, a wrong block table or a wrong mask would then barely move
# the logits the reference check reads. x4 on both sides puts the scores'
# spread near 2, as peaked as a trained model's.
QK_GAIN = 4.0
ASCII_LO, ASCII_HI = 32, 127  # [lo, hi)

EXPECTED_SIGNATURE = ("reader", "cfg", "mesh", "dtype", "quant", "group")

def ascii_column_scale(cfg) -> float:
    plain = INIT_STD * (cfg.d_model ** 0.5) * cfg.logit_scale
    return ASCII_LOGIT_STD / plain


def _seed_key(seed: int):
    import jax

    # --seed may exceed 32 signed bits: split it instead of truncating
    key = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    return jax.random.fold_in(key, seed >> 31)


def make_seeded_params(seed: int):
    """Returns a function with the loader's signature that ignores the
    reader's tensors and builds the tree from ``seed``. Its ``last_build``
    attribute holds the seconds and bytes of its last call."""

    def seeded_params(reader, cfg, mesh, dtype=None, quant="none", group=32):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from nats_llm_studio_tpu.ops.wquant import QTensor, quantizable
        from nats_llm_studio_tpu.parallel.sharding import (
            param_sharding_rules, scale_spec)

        if quant not in ("none", "int8"):
            raise NotImplementedError(
                f"seeded weights cover quant 'none' and 'int8', not {quant!r}")
        if cfg.attn_bias:
            raise NotImplementedError("seeded weights build the no-bias schema")
        t0 = time.perf_counter()
        dt = jnp.dtype(dtype or cfg.dtype)
        rules = param_sharding_rules(mesh, cfg)
        L, d, ff = cfg.n_layers, cfg.d_model, cfg.d_ff
        hq, hkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
        v = cfg.vocab_size
        col = ascii_column_scale(cfg)

        def q8(w):
            # the math of ops.wquant.quantize_weight: symmetric absmax int8
            # over the contraction (second-to-last) axis
            wf = w.astype(jnp.float32)
            amax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)
            s = amax / 127.0
            safe = jnp.where(s == 0, 1.0, s)
            q = jnp.clip(jnp.round(wf / safe), -127, 127).astype(jnp.int8)
            return QTensor(q=q, s=safe.astype(jnp.float32))

        def leaf(name, w):
            return q8(w) if quant == "int8" and quantizable(name) else w.astype(dt)

        def randn(k, shape, gain=1.0):
            # rounded to the serving dtype first, as a loaded bf16 file is
            return (jax.random.normal(k, shape, jnp.float32) * (INIT_STD * gain)).astype(dt)

        stacked = {"wq": (d, hq), "wk": (d, hkv), "wv": (d, hkv), "wo": (hq, d)}
        if cfg.is_moe:
            e = cfg.n_experts
            stacked |= {"router": (d, e), "w_gate_e": (e, d, ff),
                        "w_up_e": (e, d, ff), "w_down_e": (e, ff, d)}
        else:
            stacked |= {"w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)}

        def build(key):
            k_embed, k_head, k_blocks = jax.random.split(key, 3)
            embed = randn(k_embed, (v, d))
            loud = jnp.where(
                (jnp.arange(v) >= ASCII_LO) & (jnp.arange(v) < ASCII_HI), col, 1.0)
            # the head is drawn on its own even where the published model
            # ties it to the embedding: a random tied head gives the last
            # prompt token's own column a logit of ~12 |e|^2 x the loudness
            # (50 at these widths), and the model repeats one byte for ever.
            # The served tree holds a materialised lm_head either way.
            head = randn(k_head, (d, v)).astype(jnp.float32) * loud[None, :]
            blocks = {"attn_norm": jnp.ones((L, d), dt), "ffn_norm": jnp.ones((L, d), dt)}
            for i, (name, shape) in enumerate(stacked.items()):
                keys = jax.random.split(jax.random.fold_in(k_blocks, i), L)
                # one layer slice at a time: the f32 transient is one slice
                gain = QK_GAIN if name in ("wq", "wk") else 1.0
                blocks[name] = jax.lax.map(
                    lambda k, name=name, shape=shape, gain=gain: leaf(
                        name, randn(k, shape, gain)), keys)
            return {"embed": embed, "out_norm": jnp.ones((d,), dt),
                    "lm_head": leaf("lm_head", head), "blocks": blocks}

        def shard(name, x, stacked_axis):
            spec = rules[name]
            if isinstance(x, QTensor):
                if stacked_axis:
                    s_spec = P(spec[0], *scale_spec(P(*spec[1:])))
                else:
                    s_spec = scale_spec(spec)
                return QTensor(q=NamedSharding(mesh, spec), s=NamedSharding(mesh, s_spec))
            return NamedSharding(mesh, spec)

        shapes = jax.eval_shape(build, _seed_key(seed))
        out_shardings = {
            k: shard(k, shapes[k], False) for k in ("embed", "out_norm", "lm_head")
        }
        out_shardings["blocks"] = {
            k: shard(f"blocks.{k}", x, True) for k, x in shapes["blocks"].items()
        }
        params = jax.jit(build, out_shardings=out_shardings)(_seed_key(seed))
        jax.block_until_ready(params)
        seeded_params.last_build.update(
            seconds=time.perf_counter() - t0, seed=seed, quant=quant,
            bytes=sum(x.nbytes for x in jax.tree.leaves(params)))
        return params

    seeded_params.last_build = {}
    return seeded_params


def install(seed: int):
    """Point ``parallel.loader.load_params_sharded`` at the seeded builder
    and return it. The only program name the benchmark overrides."""
    from nats_llm_studio_tpu.parallel import loader

    target = getattr(loader, "load_params_sharded", None)
    if target is None:
        raise RuntimeError(
            "parallel.loader.load_params_sharded is gone: the benchmark's one "
            "seam for seeded weights needs a new home (see benchmark/README.md)")
    names = tuple(inspect.signature(target).parameters)
    if names != EXPECTED_SIGNATURE:
        raise RuntimeError(
            f"parallel.loader.load_params_sharded{names} no longer matches "
            f"{EXPECTED_SIGNATURE}: refusing to substitute seeded weights")
    loader.load_params_sharded = builder = make_seeded_params(seed)
    return builder
