"""Plain reference for a dense decoder with q/k/v biases (Qwen2-style), beside
the toy configuration that names it. It is here to show the way in for a
family: this file, a configuration and two manifest entries, and no edit to
``run.py`` or ``lib/``. The seeded weights take the schema (with ``bq``, ``bk``,
``bv``) from the program's own initialiser, which is the default; a family
whose leaves another initialiser of the program makes would export
``param_shapes(mcfg)`` here. ``weight_gains`` makes the biases loud.

A float32 ``jax.numpy`` forward over the prompt and the served tokens at
``highest`` precision, from the published equations (HF ``modeling_qwen2.py``):
RMSNorm, biased q/k/v projections, rotary embedding on the half-split pairs,
grouped-query attention, SwiGLU. It reads the tree the engine serves and
dequantises int8 codes a layer at a time.
"""

from __future__ import annotations

import numpy as np

# drawn this much louder than N(0, 0.02) by the seeded weights: std 0.5 beside
# projections of std ~0.6 at the toy's widths, so that a dropped bias moves
# the logits the reference check reads
weight_gains = {"bq": 25.0, "bk": 25.0, "bv": 25.0}


def model_config(hf: dict, max_seq_len: int):
    """Published config.json keys -> the program's ModelConfig."""
    from nats_llm_studio_tpu.models.config import ModelConfig

    return ModelConfig(
        arch="qwen2", vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
        n_layers=hf["num_hidden_layers"], n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"],
        head_dim=hf["hidden_size"] // hf["num_attention_heads"],
        d_ff=hf["intermediate_size"], rope_theta=float(hf["rope_theta"]),
        rms_eps=float(hf["rms_norm_eps"]), max_seq_len=max_seq_len,
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        attn_bias=True, dtype="bfloat16")


def _f32(leaf, layer=None):
    """A leaf (or one layer of a stacked one) as float32: a plain array, or
    int8 codes times their per-output-channel scale."""
    import jax.numpy as jnp

    if hasattr(leaf, "q"):
        q, s = (leaf.q, leaf.s) if layer is None else (leaf.q[layer], leaf.s[layer])
        return q.astype(jnp.float32) * s.astype(jnp.float32)
    return (leaf if layer is None else leaf[layer]).astype(jnp.float32)


def tail_logprobs(params, hf: dict, tokens, n: int, pad_to=None) -> np.ndarray:
    """Log-probabilities [n, vocab] of the token after each of the last ``n``
    positions of ``tokens``, from one full forward pass. ``pad_to`` (one
    program for every length) is not worth its code at a toy's size."""
    import jax
    import jax.numpy as jnp

    d, hq, hkv = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"]
    hd, eps = d // hq, float(hf["rms_norm_eps"])
    toks = jnp.asarray(tokens, jnp.int32)
    t = toks.shape[0]
    kv_of = jnp.arange(hq) // (hq // hkv)

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w

    inv = 1.0 / (float(hf["rope_theta"]) ** (jnp.arange(hd // 2, dtype=jnp.float32) / (hd // 2)))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rope(x):  # [t, h, hd]: rotate (first half, second half) pairs
        x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    causal = jnp.tril(jnp.ones((t, t), bool))
    b = params["blocks"]
    with jax.default_matmul_precision("highest"):
        x = params["embed"][toks].astype(jnp.float32)
        for i in range(hf["num_hidden_layers"]):
            w = {k: _f32(b[k], i) for k in b}
            h = rms(x, w["attn_norm"])
            q = rope((h @ w["wq"] + w["bq"]).reshape(t, hq, hd))
            k = rope((h @ w["wk"] + w["bk"]).reshape(t, hkv, hd))[:, kv_of]
            v = (h @ w["wv"] + w["bv"]).reshape(t, hkv, hd)[:, kv_of]
            s = jnp.einsum("thd,shd->hts", q, k) * hd ** -0.5
            p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
            x = x + jnp.einsum("hts,shd->thd", p, v).reshape(t, hq * hd) @ w["wo"]
            h = rms(x, w["ffn_norm"])
            x = x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]
        x = rms(x[-n:], params["out_norm"].astype(jnp.float32))
        return np.asarray(jax.nn.log_softmax(x @ _f32(params["lm_head"]), axis=-1), np.float32)
