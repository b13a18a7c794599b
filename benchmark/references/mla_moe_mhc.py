"""Plain reference for latent-attention (MLA) decoders with sigmoid-routed
experts beside a shared one and a multi-stream (mHC) residual, beside the
configurations that name it (``"reference": "mla_moe_mhc"``; first:
Xing4.0-29B-A4B).

A straightforward float32 ``jax.numpy`` forward over a whole prompt and the
tokens served after it: EXPANDED attention over the full sequence, no cache,
no kernel, no batching, ``jax.default_matmul_precision("highest")``, and no
import of the program's model code. It reads the published ``config.json``
keys and the very tree the engine serves, a layer at a time (a scan over each
stack's leaves) and inside an expert layer ONE EXPERT at a time, so that its
float32 copies fit beside the live engine. One compiled program a ``(T, N)``.

The equations (h a token's hidden vector, d wide; X its n = ``hc_mult``
residual streams, [n, d]):

* mHC, once around attention and once around the FFN of every layer, each
  with its own parameters: x~ = RMSNorm(vec(X)) (stream-major, no gain);
  H~_pre = a_pre (x~ Phi_pre) + b_pre, H~_post likewise, H~_res = a_res
  mat(x~ Phi_res) + b_res; H_pre = sigmoid(H~_pre), H_post = 2 sigmoid(H~_post),
  H_res = Sinkhorn(exp(clip(H~_res, ``mhc_h_res_clamp_min``, ``_max``))) with
  ``hc_sinkhorn_iters`` rounds of row then column normalisation and ``hc_eps``
  in the denominators; u = H_pre X; y = F(RMSNorm(u) gain); X <- H_res X +
  H_post^T (x) y.
* MLA: c_q = RMSNorm(h W_dq); [q_nope | q_rope] per head = c_q W_uq;
  [c | k_r] = h W_dkv; c <- RMSNorm(c); q_rope and k_r rotated, k_r ONE key
  shared by all heads; [k_nope | v] per head = c W_ukv; score_h = (q_nope_h .
  k_nope_h + q_rope_h . k_r) s, causal softmax, out = concat(o_h) W_o.
* YaRN (``rope_scaling``): inv_freq = inter (1 - m) + extra m, extra =
  theta^(-2i/dr), inter = extra / factor, m = 1 - ramp over the correction
  range of beta_fast, beta_slow; cos/sin times mscale(factor, mscale) /
  mscale(factor, mscale_all_dim); s = (dn + dr)^(-1/2) mscale(factor,
  mscale_all_dim)^2.
* Experts (layers after the first ``first_k_dense_replace``): sigma =
  sigmoid(h W_r); the ``num_experts_per_tok`` experts with the largest sigma +
  ``e_score_correction_bias``; g = sigma_chosen / (sum + 1e-20) x
  ``routed_scaling_factor``; y = sum g_e SwiGLU_e(h) + SwiGLU_shared(h). No
  token is dropped. The leading layers are a dense SwiGLU.

Departures from the published model, each also in the configuration's
``assumed``: the embedding is copied into all n streams; the final norm and
the head read the SUM of the streams; Sinkhorn normalises rows first;
RMSNorm(vec(X)) carries no gain; rotary pairs are (first half, second half)
of the rotary dims, as the program's loader lays every family out; the
multi-token-prediction module is not loaded (it changes no logit);
``n_group`` = ``topk_group`` = 1, so there is no group stage. One departure
from "everything at ``highest``": the routed experts' own three products run
at ``Precision.HIGH`` (three bf16 passes), see ``experts``.

Also here: the mapping from the published keys to the program's
``ModelConfig``, the program's initialiser for the family (``param_shapes``),
and how loud the seeded leaves are drawn (``weight_gains``).
"""

from __future__ import annotations

import math

import numpy as np

# How much louder (or quieter) than N(0, 0.02) the seeded weights draw a leaf
# (by its last name, in both stacks). At the published widths:
# - w_uq x4: scores spread ~3.4 (x1: 0.9; QK_GAIN gives GQA ~2). Sharp
#   attention is what parts fp8 from bf16 at this depth: seven layers add
#   less smooth noise than Granite's forty, for which lib/correct.py's limits
#   were read, and at x2 the fp8 control's decoded median read 1.11-1.23
#   against the limit of 1.3 and passed `correct` outright on one seed of
#   five; at x4 it reads 1.89-2.09 and fails all five limits on every seed,
#   while the served path's median goes 0.13 -> 0.28 (PERF.md, PR 29).
# - router x1: logits of std 1.2, sigma of std ~0.25, so routing follows the
#   token; e_bias x32: a selection bias of std 0.64, 2.5 x sigma's spread. It
#   widens the margin between a token's 4th and 5th expert, so bf16 rounding
#   flips them less often, and a dropped bias picks other experts for every
#   token. The price: routing is near static (8.6 of 64 experts hit a step).
# - w_down_e x0.25: with RANDOM experts a flipped expert is an unrelated
#   vector, not a near neighbour as in a trained model. At x1 single
#   positions of the served path read 6-9 against limits of 6 and 8 while its
#   medians read 0.1-0.2; the largest single position scales with this gain
#   (x0.4 with w_uq x4: 5.57 of 6.0 on one seed of three). A fault in the
#   routed path moves EVERY token's four experts and still reads far over
#   the median's limit.
# - the mixers: x~ Phi has std 2.4 at gain 1; a x20 (std 0.4) and b x25
#   (std 0.5) put the maps' logits near std 1 with a token-dependent part as
#   large as the constant one, so a fault in a, b or Sinkhorn moves logits.
weight_gains = {
    "w_uq": 4.0, "router": 1.0, "e_bias": 32.0, "w_down_e": 0.25,
    "hc_attn_a": 20.0, "hc_ffn_a": 20.0, "hc_attn_b": 25.0, "hc_ffn_b": 25.0,
}


def model_config(hf: dict, max_seq_len: int):
    """Published config.json keys -> the program's ModelConfig."""
    from nats_llm_studio_tpu.models.config import ModelConfig

    rs = hf.get("rope_scaling") or {}
    dn, dr = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
    return ModelConfig(
        arch="xing4", vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
        n_layers=hf["num_hidden_layers"], n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"], head_dim=dn + dr,
        d_ff=hf["intermediate_size"], rope_theta=float(hf["rope_theta"]),
        rms_eps=float(hf["rms_norm_eps"]), max_seq_len=max_seq_len,
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        n_experts=hf["n_routed_experts"], n_experts_used=hf["num_experts_per_tok"],
        q_lora_rank=hf["q_lora_rank"], kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=dn, qk_rope_head_dim=dr, v_head_dim=hf["v_head_dim"],
        rope_factor=float(rs.get("factor", 1.0)),
        rope_orig_ctx=int(rs.get("original_max_position_embeddings", 0)),
        rope_beta_fast=float(rs.get("beta_fast", 32.0)),
        rope_beta_slow=float(rs.get("beta_slow", 1.0)),
        rope_mscale=float(rs.get("mscale", 1.0)),
        rope_mscale_all_dim=float(rs.get("mscale_all_dim", 0.0)),
        moe_d_ff=hf["moe_intermediate_size"], n_shared_experts=hf["n_shared_experts"],
        n_dense_layers=hf["first_k_dense_replace"],
        router_scoring=hf["scoring_func"], routed_scaling=float(hf["routed_scaling_factor"]),
        hc_mult=hf["hc_mult"], hc_sinkhorn_iters=hf["hc_sinkhorn_iters"],
        hc_eps=float(hf["hc_eps"]), hc_res_clamp_min=float(hf["mhc_h_res_clamp_min"]),
        hc_res_clamp_max=float(hf["mhc_h_res_clamp_max"]), dtype="bfloat16")


def param_shapes(mcfg):
    """The tree the program would load for the family, as shapes: its own
    initialiser with the head materialised, never run."""
    import jax

    from nats_llm_studio_tpu.models import llama, mla_moe

    return jax.eval_shape(
        lambda: llama.ensure_lm_head(mla_moe.init_params(mcfg, jax.random.PRNGKey(0))))


def yarn_inv_freq(hf: dict) -> np.ndarray:
    """The rotary part's inverse frequencies [dr/2] (closed form, float64)."""
    dr, theta = hf["qk_rope_head_dim"], float(hf["rope_theta"])
    extra = theta ** (-np.arange(0, dr, 2, dtype=np.float64) / dr)
    rs = hf.get("rope_scaling") or {}
    factor = float(rs.get("factor", 1.0))
    if factor <= 1.0:
        return extra
    orig = rs["original_max_position_embeddings"]

    def corr(rot):  # the dim whose wavelength makes `rot` turns over `orig` positions
        return dr * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dr - 1)
    high = high + 0.001 if low == high else high
    m = 1.0 - np.clip((np.arange(dr // 2) - low) / (high - low), 0.0, 1.0)
    return (extra / factor) * (1.0 - m) + extra * m


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1.0 or not m else 0.1 * m * math.log(factor) + 1.0


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
    d, hq, ns = hf["hidden_size"], hf["num_attention_heads"], hf["hc_mult"]
    dn, dr, dv = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"]
    rkv = hf["kv_lora_rank"]
    eps, hc_eps = float(hf["rms_norm_eps"]), float(hf["hc_eps"])
    n_exp, top_k = hf["n_routed_experts"], hf["num_experts_per_tok"]
    rs = hf.get("rope_scaling") or {}
    factor = float(rs.get("factor", 1.0))
    scale = (dn + dr) ** -0.5 * _mscale(factor, rs.get("mscale_all_dim", 0.0)) ** 2
    rot_x = _mscale(factor, rs.get("mscale", 1.0)) / _mscale(factor, rs.get("mscale_all_dim", 0.0))

    t_real = len(tokens)
    t, rows = pad_to or (t_real, n)
    if t_real > t or n > rows or n > t_real:
        raise ValueError(f"{t_real} tokens and {n} rows do not fit pad_to {pad_to}")
    toks = jnp.asarray(list(tokens) + [0] * (t - t_real), jnp.int32)
    start = max(0, t_real - rows)

    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(yarn_inv_freq(hf), jnp.float32)
    cos, sin = jnp.cos(ang) * rot_x, jnp.sin(ang) * rot_x  # [t, dr/2]
    causal = jnp.tril(jnp.ones((t, t), bool))

    def rms(x, w=None):
        y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
        return y if w is None else y * w.astype(jnp.float32)

    def rope(x):  # [t, ..., dr]: rotate (first half, second half) pairs
        c = cos.reshape((t,) + (1,) * (x.ndim - 2) + (dr // 2,))
        s = sin.reshape(c.shape)
        x1, x2 = x[..., : dr // 2], x[..., dr // 2:]
        return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)

    def mixer(X, w, which):  # X [t, n, d] -> H_pre [t, n], H_post [t, n], H_res [t, n, n]
        z = low(rms(X.reshape(t, ns * d))) @ _f32(w[f"hc_{which}_w"])
        a, b = _f32(w[f"hc_{which}_a"]), _f32(w[f"hc_{which}_b"])
        pre = jax.nn.sigmoid(a[0] * z[:, :ns] + b[:ns])
        post = 2.0 * jax.nn.sigmoid(a[1] * z[:, ns: 2 * ns] + b[ns: 2 * ns])
        res = jnp.exp(jnp.clip(
            a[2] * z[:, 2 * ns:].reshape(t, ns, ns) + b[2 * ns:].reshape(ns, ns),
            float(hf["mhc_h_res_clamp_min"]), float(hf["mhc_h_res_clamp_max"])))
        for _ in range(hf["hc_sinkhorn_iters"]):
            res = res / (jnp.sum(res, axis=-1, keepdims=True) + hc_eps)  # rows
            res = res / (jnp.sum(res, axis=-2, keepdims=True) + hc_eps)  # columns
        return pre, post, res

    def sublayer(X, w, which, f):
        pre, post, res = mixer(X, w, which)
        y = f(rms(jnp.einsum("tn,tnd->td", pre, X), w[f"{which}_norm"]))
        return jnp.einsum("tij,tjd->tid", res, X) + post[:, :, None] * y[:, None, :]

    def attention(h, w):
        hl = low(h)
        cq = rms(hl @ _f32(w["w_dq"]), w["q_norm"])
        q = (low(cq) @ _f32(w["w_uq"])).reshape(t, hq, dn + dr)
        ckr = hl @ _f32(w["w_dkv"])
        c = low(rms(ckr[:, :rkv], w["kv_norm"]))   # what a cache would hold
        k_r = low(rope(ckr[:, rkv:]))              # ONE rotary key for all heads
        kv = (c @ _f32(w["w_ukv"])).reshape(t, hq, dn + dv)
        q_n, q_r = low(q[..., :dn]), low(rope(q[..., dn:]))

        def head(xs):  # a head at a time: [t, t] scores fit beside the engine
            qn, qr, kn, v = xs
            s = (qn @ kn.T + qr @ k_r.T) * scale
            return jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1) @ v

        o = jax.lax.map(head, tuple(z.transpose(1, 0, 2) for z in (
            q_n, q_r, kv[..., :dn], kv[..., dn:])))
        return low(o.transpose(1, 0, 2).reshape(t, hq * dv)) @ _f32(w["wo"])

    def swiglu(hl, wg, wu, wd, precision=None):
        dot = lambda a, b: jnp.dot(a, b, precision=precision)  # noqa: E731
        return dot(low(jax.nn.silu(dot(hl, wg)) * dot(hl, wu)), wd)

    def experts(h, w):
        hl = low(h)
        sig = jax.nn.sigmoid(hl @ _f32(w["router"]))
        _, idx = jax.lax.top_k(sig + _f32(w["e_bias"]), top_k)
        chosen = jnp.take_along_axis(sig, idx, axis=-1)
        g = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20) * float(
            hf["routed_scaling_factor"])
        combine = jnp.sum(jax.nn.one_hot(idx, n_exp) * g[..., None], axis=1)  # [t, E]

        def one(acc, xs):  # ONE expert's float32 copy at a time
            wg, wu, wd, c_e = xs
            # three bf16 passes (~1e-5 relative), not six: all 64 experts run
            # over all tokens, 16 x the routed work, and at `highest` that was
            # 8.7 s a forward on the chip (PERF.md, PR 29). What decides a
            # route, and every other product, stays at `highest`.
            y = swiglu(hl, _f32(wg), _f32(wu), _f32(wd), precision=jax.lax.Precision.HIGH)
            return acc + y * c_e[:, None], None

        y, _ = jax.lax.scan(one, jnp.zeros_like(h), (
            w["w_gate_e"], w["w_up_e"], w["w_down_e"], combine.T))
        return y + swiglu(hl, _f32(w["w_gate_s"]), _f32(w["w_up_s"]), _f32(w["w_down_s"]))

    def dense(h, w):
        return swiglu(low(h), _f32(w["w_gate"]), _f32(w["w_up"]), _f32(w["w_down"]))

    def layer(ffn):
        def f(X, w):
            X = sublayer(X, w, "attn", lambda h: attention(h, w))
            return sublayer(X, w, "ffn", lambda h: ffn(h, w)), None
        return f

    @jax.jit
    def forward(params, toks, start):
        x = params["embed"][toks].astype(jnp.float32)
        X = jnp.broadcast_to(x[:, None, :], (t, ns, d))  # copied into all streams
        blocks = params["blocks"]
        if "dense" in blocks:
            X, _ = jax.lax.scan(layer(dense), X, blocks["dense"])
        if "moe" in blocks:
            X, _ = jax.lax.scan(layer(experts), X, blocks["moe"])
        x = jax.lax.dynamic_slice_in_dim(jnp.sum(X, axis=1), start, rows, axis=0)
        x = low(rms(x, params["out_norm"]))
        return jax.nn.log_softmax(x @ _f32(params["lm_head"]), axis=-1)

    with jax.default_matmul_precision("highest"):
        out = np.asarray(forward(params, toks, jnp.int32(start)), np.float32)
    return out[t_real - n - start: t_real - start]
