"""Plain reference for dense Granite-3.x / Llama-style decoders, beside the
configurations that name it (``"reference": "granite_dense"``).

A straightforward float32 ``jax.numpy`` forward pass over a whole prompt and
the tokens served after it: no kernels, no cache, no batching,
``jax.default_matmul_precision("highest")``. A reference module exports
``model_config(conf, max_seq_len)`` and ``tail_logprobs(params, conf, tokens,
n)``, and may export ``param_shapes(mcfg)`` (see ``benchmark/README.md``).
It follows the published equations (HF ``modeling_granite.py``): RMSNorm,
rotary embedding on the half-split pairs, grouped-query attention with
``attention_multiplier`` as the score scale, SwiGLU, and Granite's four
multipliers (embedding x, residual x, logits /). It reads the published
``config.json`` keys, not the program's ``ModelConfig``, and the very tree the
engine serves: each layer's int8 codes are dequantised (codes x scale) one
layer at a time inside a scan over the stacked leaves, so weight quantisation
is NOT part of the difference it measures; activation precision, kernels, cache and batching are.

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


def _dense(w):
    """One weight as float32 [in, out]: a plain array, or the served tree's
    int8 leaf (codes ``q`` times per-output-channel scale ``s``). Called
    inside jit, so the float32 copy of one matrix is the only transient."""
    import jax.numpy as jnp

    if hasattr(w, "q") and hasattr(w, "s"):
        return w.q.astype(jnp.float32) * w.s.astype(jnp.float32)
    return w.astype(jnp.float32)


def last_logprobs(params, hf: dict, tokens) -> np.ndarray:
    """Log-probabilities [vocab] of the token after ``tokens`` (a 1-D list of
    ids), float32."""
    return tail_logprobs(params, hf, tokens, 1)[0]


FP8_MAX = 448.0  # float8_e4m3fn's largest finite value


def _fp8(x):
    """``x`` as an fp8 (e4m3) activation path would hold it: each row scaled
    to the format's range by its own largest entry, rounded, scaled back."""
    import jax.numpy as jnp

    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / FP8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def tail_logprobs(params, hf: dict, tokens, n: int, pad_to: tuple[int, int] | None = None,
                  lower: str | None = None) -> np.ndarray:
    """Log-probabilities [n, vocab] of the token after each of the last ``n``
    positions of ``tokens``, float32, from ONE full forward pass: with
    ``tokens`` a prompt and the first n-1 tokens served after it, row i is the
    distribution the i-th served token was drawn from (teacher-forced on what
    was served, so a served argmax that bf16 noise flipped does not derail
    the rows after it).

    ``pad_to`` (T, N): the tokens are padded to T and N rows are computed, so
    that every call of one (T, N) is ONE compiled program whatever its own
    length (a position sees only those before it: the padding behind the
    last token changes no row that is returned).

    ``lower="fp8"`` is the CONTROL, never the reference: the same forward with
    every matmul's input, and the keys and values, rounded to fp8 (e4m3, a
    scale a row), the nearest precision below the bf16 the configuration
    serves its activations and KV in. ``correct`` must tell it from the
    served path (benchmark/tests/test_control.py, PERF.md section 2)."""
    import jax
    import jax.numpy as jnp

    if lower not in (None, "fp8"):
        raise ValueError(f"unknown lower precision {lower!r}")
    low = _fp8 if lower else (lambda x: x)
    d = hf["hidden_size"]
    hq, hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf.get("head_dim") or d // hq
    eps = float(hf["rms_norm_eps"])
    emb_x = float(hf.get("embedding_multiplier", 1.0))
    res_x = float(hf.get("residual_multiplier", 1.0))
    att_x = float(hf.get("attention_multiplier", hd ** -0.5))
    logit_div = float(hf.get("logits_scaling", 1.0))
    t_real = len(tokens)
    t, rows = pad_to or (t_real, n)
    if t_real > t or n > rows or n > t_real:
        raise ValueError(f"{t_real} tokens and {n} rows do not fit pad_to {pad_to}")
    toks = jnp.asarray(list(tokens) + [0] * (t - t_real), jnp.int32)
    start = max(0, t_real - rows)  # rows [start, start + rows) are computed
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

    def attend(qkv):
        # a head at a time, so that the [t, t] scores of a long prompt fit
        # beside the engine that is still loaded
        q, k, v = qkv  # [t, hd] each
        p = jax.nn.softmax(jnp.where(causal, (q @ k.T) * att_x, -jnp.inf), axis=-1)
        return p @ v

    def layer(x, w):
        wq, wk, wv, wo, wg, wu, wd = (_dense(w[k]) for k in (
            "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"))
        h = low(rms(x, w["attn_norm"].astype(jnp.float32)))
        q = rope((h @ wq).reshape(t, hq, hd))
        k = low(rope((h @ wk).reshape(t, hkv, hd)))[:, kv_of]
        v = low((h @ wv).reshape(t, hkv, hd))[:, kv_of]
        a = jax.lax.map(attend, tuple(z.transpose(1, 0, 2) for z in (low(q), k, v)))
        a = a.transpose(1, 0, 2).reshape(t, hq * hd)
        x = x + (low(a) @ wo) * res_x
        h = low(rms(x, w["ffn_norm"].astype(jnp.float32)))
        return x + (low(jax.nn.silu(h @ wg) * (h @ wu)) @ wd) * res_x

    # ONE program a (T, N): the layers are a scan over the stacked leaves as
    # the engine holds them, a layer's float32 weights the only transient.
    # (Run layer by layer from Python it was a program per layer call and
    # per slice: 20-40 s of an empty compile cache, PR 28.)
    @jax.jit
    def forward(params, toks, start):
        x = params["embed"][toks].astype(jnp.float32) * emb_x
        x, _ = jax.lax.scan(lambda x, w: (layer(x, w), None), x, params["blocks"])
        x = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=0)
        x = low(rms(x, params["out_norm"].astype(jnp.float32)))
        head = params.get("lm_head")
        # tied and not materialised: the embedding transposed
        head = params["embed"].astype(jnp.float32).T if head is None else _dense(head)
        return jax.nn.log_softmax((x @ head) / logit_div, axis=-1)

    with jax.default_matmul_precision("highest"):
        out = np.asarray(forward(params, toks, jnp.int32(start)), np.float32)
    return out[t_real - n - start: t_real - start]
