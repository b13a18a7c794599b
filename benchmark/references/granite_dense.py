"""Plain reference for dense Granite-3.x / Llama-style decoders, beside the
configurations that name it (``"reference": "granite_dense"``).

A straightforward float32 ``jax.numpy`` forward pass over a whole prompt: no
kernels, no cache, no batching, ``jax.default_matmul_precision("highest")``.
It follows the published equations (HF ``modeling_granite.py``): RMSNorm,
rotary embedding on the half-split pairs, grouped-query attention with
``attention_multiplier`` as the score scale, SwiGLU, and Granite's four
multipliers (embedding x, residual x, logits /). It reads the published
``config.json`` keys, not the program's ``ModelConfig``, and the very tree the
engine serves: each layer's int8 codes are dequantised (codes x scale) one
layer at a time, so weight quantisation is NOT part of the difference it
measures; activation precision, kernels, cache and batching are.

Also here, because it belongs to the same family: the mapping from the
published keys to the program's ``ModelConfig`` (what the header-only GGUF's
metadata is made from).
"""

from __future__ import annotations

import numpy as np


def model_config(hf: dict, max_seq_len: int):
    """Published config.json keys -> the program's ModelConfig."""
    from nats_llm_studio_tpu.models.config import ModelConfig

    granite = hf["model_type"] == "granite"
    return ModelConfig(
        arch="granite" if granite else "llama",
        vocab_size=hf["vocab_size"],
        d_model=hf["hidden_size"],
        n_layers=hf["num_hidden_layers"],
        n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"],
        head_dim=hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"],
        d_ff=hf["intermediate_size"],
        rope_theta=float(hf["rope_theta"]),
        rms_eps=float(hf["rms_norm_eps"]),
        max_seq_len=max_seq_len,
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        embedding_scale=float(hf.get("embedding_multiplier", 1.0)),
        residual_scale=float(hf.get("residual_multiplier", 1.0)),
        attention_scale=(float(hf["attention_multiplier"])
                         if "attention_multiplier" in hf else None),
        logit_scale=1.0 / float(hf.get("logits_scaling", 1.0)),
        attn_bias=bool(hf.get("attention_bias", False)),
        dtype="bfloat16",
    )


def kv_head_of(q_head: int, n_heads: int, n_kv_heads: int) -> int:
    """Grouped-query attention: consecutive query heads share a kv head."""
    return q_head // (n_heads // n_kv_heads)


def _slice(leaf, layer: int):
    """Layer ``layer`` of a stacked leaf: a plain array, or the (codes,
    scale) pair of an int8 QTensor."""
    if hasattr(leaf, "q") and hasattr(leaf, "s"):
        return (leaf.q[layer], leaf.s[layer])
    return leaf[layer]


def _dense(w):
    """One weight as float32 [in, out]: a plain array, or int8 codes times
    their per-output-channel scale. Called inside jit, so the float32 copy
    of one matrix is the only transient."""
    import jax.numpy as jnp

    if isinstance(w, tuple):
        return w[0].astype(jnp.float32) * w[1].astype(jnp.float32)
    return w.astype(jnp.float32)


def last_logprobs(params, hf: dict, tokens) -> np.ndarray:
    """Log-probabilities [vocab] of the token after ``tokens`` (a 1-D list of
    ids), float32."""
    import jax
    import jax.numpy as jnp

    d = hf["hidden_size"]
    hq, hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf.get("head_dim") or d // hq
    eps = float(hf["rms_norm_eps"])
    emb_x = float(hf.get("embedding_multiplier", 1.0))
    res_x = float(hf.get("residual_multiplier", 1.0))
    att_x = float(hf.get("attention_multiplier", hd ** -0.5))
    logit_div = float(hf.get("logits_scaling", 1.0))
    toks = jnp.asarray(tokens, jnp.int32)
    t = toks.shape[0]
    kv_of = jnp.asarray([kv_head_of(h, hq, hkv) for h in range(hq)], jnp.int32)

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w

    pos = jnp.arange(t, dtype=jnp.float32)
    inv = 1.0 / (float(hf["rope_theta"]) ** (jnp.arange(hd // 2, dtype=jnp.float32) / (hd // 2)))
    ang = pos[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rope(x):  # [t, h, hd]: rotate (first half, second half) pairs
        x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.jit
    def layer(x, an, fn, *ws):
        wq, wk, wv, wo, wg, wu, wd = (_dense(w) for w in ws)
        h = rms(x, an)
        q = rope((h @ wq).reshape(t, hq, hd))
        k = rope((h @ wk).reshape(t, hkv, hd))[:, kv_of]
        v = (h @ wv).reshape(t, hkv, hd)[:, kv_of]
        s = jnp.einsum("thd,shd->hts", q, k) * att_x
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        a = jnp.einsum("hts,shd->thd", p, v).reshape(t, hq * hd)
        x = x + (a @ wo) * res_x
        h = rms(x, fn)
        return x + ((jax.nn.silu(h @ wg) * (h @ wu)) @ wd) * res_x

    b = params["blocks"]
    with jax.default_matmul_precision("highest"):
        x = params["embed"][toks].astype(jnp.float32) * emb_x
        for i in range(hf["num_hidden_layers"]):
            x = layer(x, b["attn_norm"][i].astype(jnp.float32),
                      b["ffn_norm"][i].astype(jnp.float32),
                      *(_slice(b[k], i) for k in
                        ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")))
        x = rms(x[-1], params["out_norm"].astype(jnp.float32))
        head = params.get("lm_head")
        if head is None:  # tied and not materialised: the embedding transposed
            head = params["embed"].T
        elif hasattr(head, "q"):
            head = (head.q, head.s)
        logits = jax.jit(lambda x, w: (x @ _dense(w)) / logit_div)(x, head)
        return np.asarray(jax.nn.log_softmax(logits), np.float32)
