"""Where ``chip_smoke.py --chips 4`` gets its logprob tolerance from.

tp=4 and one chip run the same int8 weights and bf16 activations; what
differs is that every row-sharded contraction (wo, w_down) becomes four
partial sums, each rounded to bf16 before the all-reduce. Attention itself is
per head, so the heads split changes nothing inside it. This script measures,
on the CPU and independently of any chip reading, how far that reorder alone
moves the first token's top-5 logprobs through a random 32-layer pre-norm
model built like the smoke's GGUF (one seeded block under every layer,
N(0, 0.02)-scaled weights, printable-ASCII head rows 8x louder: logit std 10),
and how far two attention faults a broken heads split could cause move them.

    JAX_PLATFORMS=cpu python scripts/tp_reorder_noise.py

Read on 2026-09-26 (d=1024, this file as committed): reorder alone, 32
prompts of 64 tokens: median 0.63 / p90 0.96 / max 1.24; 32 of 547 tokens:
median 0.44 / p90 0.82 / max 0.91 — no growth with length. (The model is
chaotic: an equivalent rewrite of this file moved the maxima to 1.75 and 1.64
with medians 0.68 and 0.73; treat the two as draws of one distribution.) The
last shard's heads reading each other's K/V: no top-5 token shared, 8 of 8.
The last shard's heads missing the last 35 of 547 keys: median 2.14, max 3.90,
3 of 8 over 2.5. Hence chip_smoke's rule: every request within 2.5, the median
of the short-prompt requests and the median of the long-prompt requests each
within 1.0, and the argmax inside the other side's top-5.
"""

from __future__ import annotations

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import jax.numpy as jnp
import numpy as np

D, FF, L, H, HD, V = 1024, 3584, 32, 8, 128, 512
STD = 0.02 * np.sqrt(4096 / D)  # per-element activations as at d=4096
BF = jnp.bfloat16


def make_params(seed: int = 0) -> dict:
    rng = np.random.default_rng(seed)

    def n(*shape):
        return jnp.asarray(rng.standard_normal(shape, dtype=np.float32) * STD, BF)

    head = rng.standard_normal((D, V), dtype=np.float32) * STD
    head[:, 32:127] *= 8
    return {"embed": n(V, D), "head": jnp.asarray(head, BF),
            "wq": n(D, H * HD), "wk": n(D, H * HD), "wv": n(D, H * HD),
            "wo": n(H * HD, D), "wg": n(D, FF), "wu": n(D, FF), "wd": n(FF, D)}


def rms(x):
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + 1e-5)).astype(BF)


def row_parallel(x, w, parts: int):
    """``x @ w`` with the contraction split in ``parts``, each partial
    rounded to bf16 before the sum — what tp does to wo and w_down."""
    if parts == 1:
        return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(BF)
    acc = sum(
        jnp.dot(a, b, preferred_element_type=jnp.float32).astype(BF).astype(jnp.float32)
        for a, b in zip(jnp.split(x, parts, -1), jnp.split(w, parts, 0)))
    return acc.astype(BF)


def first_token_logprobs(p: dict, toks, parts: int, fault: str = ""):
    t = toks.shape[0]
    x = p["embed"][toks]
    mask = jnp.broadcast_to(jnp.tril(jnp.ones((t, t), bool)), (H, t, t))
    if fault == "dropped_tail":  # the last shard's heads miss the last 35 keys
        mask = mask.at[H - H // 4:, :, t - 35:].set(False) | jnp.eye(t, dtype=bool)
    for _ in range(L):
        h = rms(x)
        q, k, v = (jnp.dot(h, p[w]).reshape(t, H, HD) for w in ("wq", "wk", "wv"))
        if fault == "swapped_heads":  # the last shard's heads read each other's K/V
            order = jnp.array([*range(H - 2), H - 1, H - 2])
            k, v = k[:, order], v[:, order]
        scores = jnp.einsum("thd,shd->hts", q, k, preferred_element_type=jnp.float32)
        probs = jax.nn.softmax(jnp.where(mask, scores / np.sqrt(HD), -1e30), -1).astype(BF)
        attn = jnp.einsum("hts,shd->thd", probs, v).reshape(t, H * HD)
        x = x + row_parallel(attn, p["wo"], parts)
        h = rms(x)
        gated = jax.nn.silu(jnp.dot(h, p["wg"]).astype(jnp.float32)).astype(BF) * jnp.dot(h, p["wu"])
        x = x + row_parallel(gated, p["wd"], parts)
    return jax.nn.log_softmax(jnp.dot(rms(x[-1]), p["head"], preferred_element_type=jnp.float32))


def top5_diff(a: np.ndarray, b: np.ndarray) -> float:
    """chip_smoke.compare_tp's measure: max |difference| over the tokens both
    top-5 lists hold; nan when the argmax is missing or fewer than two match."""
    ta, tb = np.argsort(-a)[:5], np.argsort(-b)[:5]
    shared = sorted(set(ta) & set(tb))
    if ta[0] not in tb or len(shared) < 2:
        return float("nan")
    return float(np.max(np.abs(a[shared] - b[shared])))


def main() -> None:
    p = make_params()
    f = jax.jit(first_token_logprobs, static_argnums=(2, 3))
    for fault, lengths, n in (("", (64, 547), 32), ("swapped_heads", (547,), 8),
                              ("dropped_tail", (547,), 8)):
        for t in lengths:
            diffs = []
            for i in range(n):
                toks = jnp.asarray(np.random.default_rng(1000 + i).integers(32, 127, t))
                diffs.append(top5_diff(np.asarray(f(p, toks, 1, "")),
                                       np.asarray(f(p, toks, 4, fault))))
            d = np.sort(np.asarray(diffs))  # nan (no agreement at all) sorts last
            print(f"{fault or 'reorder_only'} T={t} n={n}: median {d[n // 2]:.2f} "
                  f"p90 {d[int(0.9 * n)]:.2f} max {d[-1]:.2f} over_2.5 {int(np.sum(~(d <= 2.5)))}")


if __name__ == "__main__":
    main()
