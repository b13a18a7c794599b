"""Transformer building blocks: RMSNorm, RoPE, GQA attention, SwiGLU.

Numerics policy (TPU-first): inputs/weights may be bf16 (MXU-native); all
reductions — norms, softmax — run in f32 and cast back. Shapes are static and
batch-major so XLA tiles matmuls onto the MXU without relayout.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(
    x: jax.Array, weight: jax.Array, eps: float = 1e-5, plus_one: bool = False
) -> jax.Array:
    """Root-mean-square layer norm (no mean subtraction, no bias).

    ``plus_one`` applies gemma's ``x * (1 + w)`` convention (the GGUF stores
    w, not 1+w — matching llama.cpp's build_gemma)."""
    xf = x.astype(jnp.float32)
    rrms = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    y = (xf * rrms).astype(x.dtype)
    return y * (weight + 1) if plus_one else y * weight


def rope_cos_sin(
    positions: jax.Array, head_dim: int, theta: float = 10000.0
) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables for rotary position embedding.

    positions: int32 [...]; returns (cos, sin) each [..., head_dim // 2] f32.
    """
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(angles), jnp.sin(angles)


def yarn_frequencies(dim: int, theta: float, factor: float = 1.0, orig_ctx: int = 0,
                     beta_fast: float = 32.0, beta_slow: float = 1.0) -> np.ndarray:
    """Inverse frequencies [dim/2] of ``dim`` rotary dims. Factor 1 is plain
    rope; otherwise YaRN blends the scaled and the unscaled frequency by a
    ramp over the correction range of ``beta_fast`` / ``beta_slow``."""
    i = np.arange(0, dim, 2, dtype=np.float64)
    extra = 1.0 / (theta ** (i / dim))
    if factor <= 1.0 or not orig_ctx:
        return extra.astype(np.float32)
    inter = extra / factor

    def corr_dim(rotations: float) -> float:
        return dim * math.log(orig_ctx / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    m = 1.0 - ramp
    return (inter * (1 - m) + extra * m).astype(np.float32)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate pairs (x[2i], x[2i+1]) — GGUF/"NEOX" interleaving is handled by
    the weight loader, so here the pairing is (first half, second half).

    x: [B, T, H, D]; cos/sin: [B, T, D/2] (broadcast over heads).
    """
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[:, :, None, :].astype(x.dtype)
    s = sin[:, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def gqa_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: jax.Array,
    scale: float,
) -> jax.Array:
    """Grouped-query attention with f32 softmax.

    q: [B, T, Hq, D]; k, v: [B, S, Hkv, D]; mask: bool [B, T, S] (True = may
    attend). Hq must be a multiple of Hkv (the group size). Returns
    [B, T, Hq, D] in q.dtype.
    """
    b, t, hq, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, t, hkv, g, d)
    logits = jnp.einsum("bthgd,bshd->bhgts", qg, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    logits = jnp.where(mask[:, None, None, :, :], logits, jnp.float32(-1e30))
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgts,bshd->bthgd", probs.astype(v.dtype), v)
    return out.reshape(b, t, hq, d)


def gqa_attention_hmajor(
    q: jax.Array,
    k,
    v,
    mask: jax.Array,
    scale: float,
) -> jax.Array:
    """gqa_attention over a heads-major cache.

    q: [B, T, Hq, D]; k, v: [B, Hkv, S, D] (the KV-cache layout — per-head
    slabs contiguous so decode DMA streams sequentially) as arrays in
    q.dtype OR int8 ``KVQ`` slabs (ops/kvcache.py). Quantized slabs never
    materialize bf16: the k scales fold onto the scores' S axis after the
    QK dot, and the v scales fold into the probabilities before the PV dot,
    so both MXU reads stream int8 codes. mask: bool [B, T, S]. Returns
    [B, T, Hq, D] in q.dtype.
    """
    from .kvcache import KVQ

    b, t, hq, d = q.shape
    hkv, s = k.shape[1], k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, t, hkv, g, d)
    if isinstance(k, KVQ):
        logits = jnp.einsum(
            "bthgd,bhsd->bhgts", qg, k.q.astype(q.dtype),
            preferred_element_type=jnp.float32,
        ) * k.s[:, :, None, None, :]
    else:
        logits = jnp.einsum(
            "bthgd,bhsd->bhgts", qg, k, preferred_element_type=jnp.float32
        )
    logits = logits * scale
    logits = jnp.where(mask[:, None, None, :, :], logits, jnp.float32(-1e30))
    probs = jax.nn.softmax(logits, axis=-1)
    if isinstance(v, KVQ):
        pv = (probs * v.s[:, :, None, None, :]).astype(q.dtype)
        out = jnp.einsum("bhgts,bhsd->bthgd", pv, v.q.astype(q.dtype))
    else:
        out = jnp.einsum("bhgts,bhsd->bthgd", probs.astype(v.dtype), v)
    return out.reshape(b, t, hq, d)


def swiglu(x: jax.Array, w_gate, w_up, w_down, act: str = "silu") -> jax.Array:
    """Gated MLP: down( act(x @ gate) * (x @ up) ).

    ``act`` selects the gate nonlinearity — "silu" (llama/granite/mixtral/
    qwen2 SwiGLU) or "gelu" (gemma GeGLU, tanh approximation as ggml uses).
    Weights are [d_in, d_out] row-major (plain ``x @ w``), stored bf16 or
    weight-only int8 (ops.wquant.QTensor).
    """
    from .wquant import mm

    g = mm(x, w_gate)
    gate = jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g, approximate=True)
    return mm(gate * mm(x, w_up), w_down)
