"""Plain reference for latent-attention (MLA) decoders with sigmoid-routed
experts beside shared ones, ONE residual stream and NO query rank, beside
the configurations that name it (``"reference": "mla_moe_plain"``; first:
kanana-2-30b-a3b-instruct-2601, ``model_type`` ``deepseek_v3``).

A straightforward float32 ``jax.numpy`` forward over a whole prompt and the
tokens served after it: expanded attention over the full sequence, no cache,
no kernel, no batching, ``jax.default_matmul_precision("highest")``, and no
import of the program's model code. It reads the published ``config.json``
keys and the very tree the engine serves. So that ONE forward over ~25.6k
positions fits in the ~4 GB a served tree and its pool leave on the chip,
nothing is float32 for longer than it is used: a layer's small leaves are a
scan's slice, attention runs a HEAD at a time and inside a head a block of
queries at a time over the keys that block can see (a static causal slice, so
the upper half of the score plane is never computed), an expert layer
upcasts ONE EXPERT of the closed-over stacks at a time, and the head a slice
of the vocabulary at a time. One compiled program a ``(T, N)``.

The layer (h the stream [T, d], pre-norm):

* a = RMSNorm(h); q = a W_q as [T, H, nope + rope], the rope part rotated;
  [c' | k_r'] = a W_dkv; c = RMSNorm(c'); k_r = k_r' rotated, ONE key for
  all heads; [k_nope_h | v_h] = c W_ukv per head; score_h = (q_nope_h .
  k_nope_h + q_rope_h . k_r) (nope + rope)^(-1/2), causal softmax, o_h = sum
  p v_h; h += concat(o_h) W_o.
* f = RMSNorm(h); the first ``first_k_dense_replace`` layers: h += SwiGLU(f)
  of width ``intermediate_size``; the others: sigma = sigmoid(f W_r), the
  ``num_experts_per_tok`` experts with the largest sigma + selection bias,
  g = sigma_chosen / (sum + 1e-20) x ``routed_scaling_factor``; h +=
  SwiGLU_shared(f) + sum g_e SwiGLU_e(f). No token is dropped.
* Rotary: plain, theta^(-2i/dr) (``rope_scaling`` null; a configuration with
  a scaling block is refused: ``mla_moe_mhc.py`` has the YaRN equations).
* Final RMSNorm, head (untied).

Departures from the published description, each also in the configuration's
``assumed``: rotary pairs are (first half, second half) of the rotary dims, as
the program's loader lays every family out (the checkpoint's interleaved
pairs would be permuted on load); the ``n_shared_experts`` shared experts are
ONE SwiGLU of width ``n_shared_experts x moe_intermediate_size`` (the same
sum); ``n_group`` = ``topk_group`` = 1, so there is no group stage. One
departure from "everything at ``highest``": the routed experts' own three
products run at ``Precision.HIGH`` (three bf16 passes), see ``experts``.

Also here: the mapping from the published keys to the program's
``ModelConfig``, the program's initialiser for the family (``param_shapes``),
and how loud the seeded leaves are drawn (``weight_gains``).
"""

from __future__ import annotations

import numpy as np

# How much louder (or quieter) than N(0, 0.02) the seeded weights draw a leaf
# (by its last name, in both stacks). ``wq`` is x4 by lib/weights.py's own
# rule (QK_GAIN). At the published widths (readings: PERF.md, PR 44):
# - w_ukv x2: with wq x4 at hidden 2,048 (Xing: 3,584 through a rank of 768)
#   the scores spread too little for fp8 to part from bf16 at six layers;
#   the key's nope half is louder by this, and the values with it.
# - router x0, e_bias x32 (Laguna's silent router, PERF.md PR 37): the router's
#   matrix is ZERO, every score is sigmoid(0) and the selection bias alone
#   picks, so every token of a layer takes the same six experts, a decode
#   step streams exactly six a layer whatever the seed, and every gate is
#   2.448 / 6. With a live router (x1) and both routing leaves pinned
#   (`fixed_draws`), the seed's OTHER weights still moved the activations and
#   so how many experts a step hit: 9.19-9.98 of 128 over six seeds, 0.6 % of
#   `out_tok_s` by that alone (two sets of the same six seeds: 0.29 % and
#   0.60 % against the 0.5 % a new cell is admitted under; the seed with 9.98
#   read lowest in both sets, the one with 9.19 highest). The price: routing
#   is static, and the chip's check cannot see a fault in the router's SCORES,
#   only in the picks; tests/test_mla_moe_plain*.py hold the router to the
#   reference on the CPU with a live one. A balanced router would hit ~45.
# - w_down_e x0.25: with RANDOM experts a wrong expert is an unrelated
#   vector, not a near neighbour as in a trained model; the largest single
#   position scales with this gain. A fault in the routed path moves EVERY
#   token's six experts and still reads far over the median's limit.
weight_gains = {"w_ukv": 2.0, "router": 0.0, "e_bias": 32.0, "w_down_e": 0.25}

# queries scored together inside a head: [block, keys <= T] float32. A run's
# 25,600 positions are 4 blocks: 2.2 GB of temporaries and 17 s of compile for
# a described v5e, where 16 blocks of 1,600 were 1.05 GB and 44 s (a block is
# unrolled, so that its keys are a static causal slice); the compile is inside
# a run's 360 s (PERF.md, PR 44)
Q_BLOCK = 6400
# columns of the head upcast and multiplied together
VOCAB_BLOCK = 16384


def model_config(hf: dict, max_seq_len: int):
    """Published config.json keys -> the program's ModelConfig."""
    from nats_llm_studio_tpu.models.config import ModelConfig

    if hf.get("rope_scaling") or hf.get("q_lora_rank"):
        raise ValueError("mla_moe_plain knows plain rotary and one query matrix only; this "
                         f"configuration has rope_scaling {hf.get('rope_scaling')!r} and "
                         f"q_lora_rank {hf.get('q_lora_rank')!r} (see mla_moe_mhc.py)")
    dn, dr = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
    cfg = ModelConfig(
        arch="deepseek2", vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
        n_layers=hf["num_hidden_layers"], n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"], head_dim=dn + dr,
        d_ff=hf["intermediate_size"], rope_theta=float(hf["rope_theta"]),
        rms_eps=float(hf["rms_norm_eps"]), max_seq_len=max_seq_len,
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        n_experts=hf["n_routed_experts"], n_experts_used=hf["num_experts_per_tok"],
        q_lora_rank=0, kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=dn, qk_rope_head_dim=dr, v_head_dim=hf["v_head_dim"],
        moe_d_ff=hf["moe_intermediate_size"], n_shared_experts=hf["n_shared_experts"],
        n_dense_layers=hf["first_k_dense_replace"],
        router_scoring=hf["scoring_func"], routed_scaling=float(hf["routed_scaling_factor"]),
        hc_mult=1, dtype="bfloat16")
    _require_the_plain_form(cfg)
    return cfg


def _require_the_plain_form(mcfg) -> None:
    """A program whose latent-attention family always has the query's
    low-rank pair and the stream mixers (a commit before the plain form)
    cannot run this configuration: say so before anything is served, not in
    the reference check minutes later."""
    blocks = param_shapes(mcfg)["blocks"]
    leaves = {k for stack in blocks.values() for k in stack}
    if "wq" not in leaves or any(k.startswith("hc_") or k == "w_dq" for k in leaves):
        raise RuntimeError(
            "this program's latent-attention family cannot express a model with one "
            f"query matrix and one residual stream (its tree has {sorted(leaves)}): "
            "models/mla_moe.py has to follow q_lora_rank == 0 and hc_mult == 1")


def param_shapes(mcfg):
    """The tree the program would load for the family, as shapes: its own
    initialiser with the head materialised, never run."""
    import jax

    from nats_llm_studio_tpu.models import llama, mla_moe

    return jax.eval_shape(
        lambda: llama.ensure_lm_head(mla_moe.init_params(mcfg, jax.random.PRNGKey(0))))


def inv_freq(hf: dict) -> np.ndarray:
    """The rotary part's inverse frequencies [dr/2] (float64)."""
    dr = hf["qk_rope_head_dim"]
    return float(hf["rope_theta"]) ** (-np.arange(0, dr, 2, dtype=np.float64) / dr)


FP8_MAX = 448.0  # float8_e4m3fn's largest finite value


def _fp8(x):
    """``x`` as an fp8 (e4m3) path would hold it: a scale a row."""
    import jax.numpy as jnp

    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / FP8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _f32(w):
    """A leaf as float32: a plain array, or int8 codes times their scale."""
    import jax.numpy as jnp

    if hasattr(w, "q") and hasattr(w, "s"):
        return w.q.astype(jnp.float32) * w.s.astype(jnp.float32)
    return w.astype(jnp.float32)


def _query_blocks(t: int) -> int:
    """The fewest equal blocks of at most Q_BLOCK queries that ``t`` splits into."""
    n = -(-t // Q_BLOCK)
    while t % n:
        n += 1
    return n


def tail_logprobs(params, hf: dict, tokens, n: int, pad_to: tuple[int, int] | None = None,
                  lower: str | None = None) -> np.ndarray:
    """Log-probabilities [n, vocab] of the token after each of the last ``n``
    positions of ``tokens``, float32, from ONE full forward (teacher-forced
    on what was served). ``pad_to`` (T, N): pad the tokens to T and compute N
    rows, one compiled program for every call of a run. ``lower="fp8"`` is
    the CONTROL, never the reference: every matmul's input and the cached
    latent and rotary key rounded to fp8 (e4m3, a scale a row)."""
    import jax
    import jax.numpy as jnp

    if lower not in (None, "fp8"):
        raise ValueError(f"unknown lower precision {lower!r}")
    low = _fp8 if lower else (lambda x: x)
    hq = hf["num_attention_heads"]
    dn, dr, dv = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"]
    rkv, eps = hf["kv_lora_rank"], float(hf["rms_norm_eps"])
    top_k = hf["num_experts_per_tok"]
    scale = (dn + dr) ** -0.5

    t_real = len(tokens)
    t, rows = pad_to or (t_real, n)
    if t_real > t or n > rows or n > t_real:
        raise ValueError(f"{t_real} tokens and {n} rows do not fit pad_to {pad_to}")
    rows = min(rows, t)
    toks = jnp.asarray(list(tokens) + [0] * (t - t_real), jnp.int32)
    start = max(0, t_real - rows)
    n_q = _query_blocks(t)
    qb = t // n_q

    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq(hf), jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)  # [t, dr/2]

    def rms(x, w=None):
        y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
        return y if w is None else y * w.astype(jnp.float32)

    def rope(x):  # [t, dr]: rotate (first half, second half) pairs
        x1, x2 = x[..., : dr // 2], x[..., dr // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    def attention(h, w):
        hl = low(h)
        ckr = hl @ _f32(w["w_dkv"])
        c = low(rms(ckr[:, :rkv], w["kv_norm"]))   # what a cache would hold
        k_r = low(rope(ckr[:, rkv:]))              # ONE rotary key for all heads
        by_head = (w["wq"].reshape(-1, hq, dn + dr).transpose(1, 0, 2),
                   w["w_ukv"].reshape(rkv, hq, dn + dv).transpose(1, 0, 2))

        def head(xs):  # one head: its query, its keys and values from the latents
            wq_h, wukv_h = xs
            q = hl @ _f32(wq_h)
            q_n, q_r = low(q[:, :dn]), low(rope(q[:, dn:]))
            kv = c @ _f32(wukv_h)
            k_n, v = kv[:, :dn], kv[:, dn:]
            out = []
            for i in range(n_q):  # a block of queries over the keys it can see
                lo, hi = i * qb, (i + 1) * qb
                s = (q_n[lo:hi] @ k_n[:hi].T + q_r[lo:hi] @ k_r[:hi].T) * scale
                seen = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
                out.append(jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1) @ v[:hi])
            return jnp.concatenate(out, axis=0)

        o = jax.lax.map(head, by_head)  # [hq, t, dv]
        return low(o.transpose(1, 0, 2).reshape(t, hq * dv)) @ _f32(w["wo"])

    def swiglu(hl, wg, wu, wd, precision=None):
        dot = lambda a, b: jnp.dot(a, b, precision=precision)  # noqa: E731
        return dot(low(jax.nn.silu(dot(hl, wg)) * dot(hl, wu)), wd)

    def experts(h, w, stacks, layer):
        hl = low(h)
        sig = jax.nn.sigmoid(hl @ _f32(w["router"]))
        _, idx = jax.lax.top_k(sig + _f32(w["e_bias"]), top_k)
        chosen = jnp.take_along_axis(sig, idx, axis=-1)
        g = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20) * float(
            hf["routed_scaling_factor"])
        n_exp = sig.shape[-1]
        combine = jnp.sum(jax.nn.one_hot(idx, n_exp) * g[..., None], axis=1)  # [t, E]

        def one(acc, xs):  # ONE expert's float32 copy at a time, out of the whole stacks
            e, c_e = xs
            wg, wu, wd = (_f32(s[layer, e]) for s in stacks)
            # three bf16 passes (~1e-5 relative), not six: every expert runs
            # over all tokens, 21 x the routed work. What decides a route, and
            # every other product, stays at `highest`.
            y = swiglu(hl, wg, wu, wd, precision=jax.lax.Precision.HIGH)
            return acc + y * c_e[:, None], None

        y, _ = jax.lax.scan(one, jnp.zeros_like(h), (jnp.arange(n_exp), combine.T))
        return y + swiglu(hl, _f32(w["w_gate_s"]), _f32(w["w_up_s"]), _f32(w["w_down_s"]))

    def dense(h, w):
        return swiglu(low(h), _f32(w["w_gate"]), _f32(w["w_up"]), _f32(w["w_down"]))

    def logprobs(x, head):  # the head a slice of the vocabulary at a time
        vocab = head.shape[-1]
        parts = [x @ _f32(head[:, at: at + VOCAB_BLOCK]) for at in range(0, vocab, VOCAB_BLOCK)]
        return jax.nn.log_softmax(jnp.concatenate(parts, axis=-1), axis=-1)

    stack_names = ("w_gate_e", "w_up_e", "w_down_e")

    @jax.jit
    def forward(params, toks, start):
        h = params["embed"][toks].astype(jnp.float32)
        blocks = params["blocks"]
        if "dense" in blocks:
            def dense_layer(h, w):
                h = h + attention(rms(h, w["attn_norm"]), w)
                return h + dense(rms(h, w["ffn_norm"]), w), None

            h, _ = jax.lax.scan(dense_layer, h, blocks["dense"])
        if "moe" in blocks:
            stacks = tuple(blocks["moe"][k] for k in stack_names)
            small = {k: v for k, v in blocks["moe"].items() if k not in stack_names}

            def moe_layer(h, xs):
                w, layer = xs
                h = h + attention(rms(h, w["attn_norm"]), w)
                return h + experts(rms(h, w["ffn_norm"]), w, stacks, layer), None

            h, _ = jax.lax.scan(moe_layer, h, (small, jnp.arange(stacks[0].shape[0])))
        x = jax.lax.dynamic_slice_in_dim(h, start, rows, axis=0)
        return logprobs(low(rms(x, params["out_norm"])), params["lm_head"])

    with jax.default_matmul_precision("highest"):
        out = np.asarray(forward(params, toks, jnp.int32(start)), np.float32)
    return out[t_real - n - start: t_real - start]
