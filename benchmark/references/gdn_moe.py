"""Plain reference for decoders of gated-delta-rule linear-attention layers
beside gated softmax-attention layers with softmax-routed experts in every
layer, beside the configurations that name it (``"reference": "gdn_moe"``;
first: Qwen3-Next-80B-A3B-Instruct, HF ``qwen3_next``).

A straightforward float32 ``jax.numpy`` forward over a whole prompt and the
tokens served after it: the delta rule ONE TOKEN AT A TIME in a ``lax.scan``
(never the chunked form: the chunked form is what it checks), causal attention
a block of queries over a block of keys at a time, the experts one at a time
over the rows that picked them, no cache, no kernel, no batching,
``jax.default_matmul_precision("highest")``, and no import of the program's
model code. It reads the published ``config.json`` keys and the very tree the
engine serves, a layer at a time (one layer's 128 experts in float32 are
1.6 GB; an expert's copy is made when it is computed). One compiled program a
``(T, N)``.

The equations (h a token's hidden vector; ``norm(x) = x / sqrt(mean(x^2) +
eps) g`` with a plain gain g, which is the published ``1 + w``):

* Layer l is FULL where (l + 1) % ``full_attention_interval`` == 0, else
  LINEAR: x <- x + Mixer(norm(x)); x <- x + MoE(norm(x)). Final norm, untied
  head.
* Linear (Gated DeltaNet), H_k = ``linear_num_key_heads``, H_v =
  ``linear_num_value_heads``, d_k, d_v the two head dims, K =
  ``linear_conv_kernel_dim``: [q | k | v | z] = h W_qkvz, [b | a] = h W_ba;
  (q, k, v) together through a depthwise causal convolution of K taps without
  bias, then SiLU; q, k L2-normalised a head (eps 1e-6), q x d_k^-0.5; key
  head i serves the r = H_v / H_k value heads i r .. i r + r - 1; beta =
  sigmoid(b); alpha =
  exp(-exp(A_log) softplus(a + dt_bias)); a value head with S [d_k, d_v],
  S_0 = 0: S' = alpha S; u = beta (v - S'^T k); S = S' + k u^T; o = S^T q;
  y = concat_heads(o / rms(o) g_o * SiLU(z)) W_o.
* Full: [q | gate] = h W_q (each H x D), k = h W_k, v = h W_v; q, k norm over
  a head (gains q_norm, k_norm); rotary on the first ``partial_rotary_factor``
  x D dims at ``rope_theta``, (first half, second half) pairs; causal softmax
  at D^-0.5; out = (attn * sigmoid(gate)) W_o.
* MoE: p = softmax(h W_r) over ALL the experts; the ``num_experts_per_tok``
  largest, w_i = p_i / their sum; y = sum_i w_i SwiGLU_i(h) + sigmoid(h .
  w_sg) SwiGLU_shared(h).

**The chip's share** (``expert_parallel`` in the file: ``chips`` share a
layer, this one is ``rank``): ``num_experts`` in the file counts the experts
HELD; the router keeps chips x that many outputs, the picks and their weights
are over all of them, and only a pick e with e mod chips == rank is computed,
on place e // chips of the served stacks. What the absent experts would have
added is left out, as in the program; the shared expert is computed whole.

Departures from the published model, each also in the configuration's
``assumed``: the in-projections' columns lie plainly ([q | k | v | z],
[b | a], [q | gate]) where the published tensors interleave them by head; the
gains are plain where the published ones are zero-centred; the served state is
float32 beside a bfloat16 model; no multi-token-prediction layer.

Also here: the mapping from the published keys to the program's
``ModelConfig``, the program's initialiser for the family (``param_shapes``),
and how loud the seeded leaves are drawn (``weight_gains``).
"""

from __future__ import annotations

import numpy as np

# How much louder (or quieter) than N(0, 0.02) the seeded weights draw a leaf.
# ``lib/weights.py`` draws EVERY leaf N(0, 0.02 x gain), a_log and dt_bias too.
# At gain 1 every head has exp(A_log) = 1 and softplus(~0) = 0.69: alpha = 0.5
# a token, a state that forgets in two or three tokens, and a carry that is
# wrong across a chunk's or an admit's edge would move no logit. The gains
# spread the heads' time constants as ``references/ssm_hybrid.py``'s do:
# - dt_bias x100: N(0, 2): softplus from ~0.05 to ~4; a_log x25: N(0, 0.5):
#   exp(A_log) from 0.4 to 2.7; together alpha a token from 0.98 (a memory of
#   ~50 tokens) to ~0;
# - conv_w x25: taps of N(0, 0.5), so the convolution mixes its four inputs (at
#   x1 silu sees ~0 and is linear, and a tail off by one moves nothing);
# - w_ba x2: beta and the decay vary with the token;
# - shared_gate x2: a gate that varies from 0.1 to 0.9 with the token;
# - w_down_e x0.25: with RANDOM experts a flipped pick is an unrelated vector,
#   so the largest single difference scales with this gain
#   (references/mla_moe_mhc.py, PR 29);
# - router x0: the silent router of laguna-xs.2 and kanana-2: every softmax
#   score is equal and ``lax.top_k`` takes the ten lowest ids, here and in the
#   program alike, of which a chip at rank 0 of 4 holds three (0, 4, 8). Every
#   seed then streams the same three experts a layer a step. A live router
#   (x4) was tried first on the chip and spread ``out_tok_s`` 0.61 % over four
#   seeds and 0.60 % over six more, over half its bound (the configuration's
#   ``assumed`` has the readings). The CPU tests draw it at x4.
# wq / wk keep ``lib/weights.py``'s QK_GAIN 4: wq's second half is the output
# gate's, which is then well away from 0.5.
weight_gains = {"dt_bias": 100.0, "a_log": 25.0, "conv_w": 25.0, "w_ba": 2.0,
                "shared_gate": 2.0, "w_down_e": 0.25, "router": 0.0}


def layer_kinds(hf: dict) -> list[str]:
    every = hf["full_attention_interval"]
    return ["attention" if (i + 1) % every == 0 else "linear"
            for i in range(hf["num_hidden_layers"])]


def share(hf: dict) -> tuple[int, int]:
    """(chips that share a layer's experts, this chip's rank)."""
    ep = hf.get("expert_parallel") or {}
    return int(ep.get("chips", 1)), int(ep.get("rank", 0))


def model_config(hf: dict, max_seq_len: int):
    """Published config.json keys -> the program's ModelConfig."""
    from nats_llm_studio_tpu.models.config import ModelConfig

    if hf.get("mlp_only_layers") or hf.get("decoder_sparse_step", 1) != 1:
        raise NotImplementedError("dense MLP layers among the expert layers")
    if hf.get("rope_scaling"):
        raise NotImplementedError("a scaled rotary table")
    if not hf.get("norm_topk_prob", True):
        raise NotImplementedError("picks that are not renormalised")
    chips, rank = share(hf)
    return ModelConfig(
        arch="qwen3next", vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
        n_layers=hf["num_hidden_layers"], n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"], head_dim=hf["head_dim"],
        d_ff=hf["intermediate_size"], rope_theta=float(hf["rope_theta"]),
        rms_eps=float(hf["rms_norm_eps"]), max_seq_len=max_seq_len,
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        n_experts=hf["num_experts"] * chips, n_experts_used=hf["num_experts_per_tok"],
        moe_d_ff=hf["moe_intermediate_size"],
        n_shared_experts=hf["shared_expert_intermediate_size"] // hf["moe_intermediate_size"],
        router_scoring="softmax", shared_gate=True, moe_ep_size=chips, moe_ep_rank=rank,
        layer_types=tuple(layer_kinds(hf)),
        lin_k_heads=hf["linear_num_key_heads"], lin_v_heads=hf["linear_num_value_heads"],
        lin_k_dim=hf["linear_key_head_dim"], lin_v_dim=hf["linear_value_head_dim"],
        ssm_conv=hf["linear_conv_kernel_dim"],
        rope_dim=int(hf["head_dim"] * float(hf.get("partial_rotary_factor", 1.0))),
        attn_out_gate=True, qk_norm=True, dtype="bfloat16")


def param_shapes(mcfg):
    """The tree the program would load for the family, as shapes: its own
    initialiser with the head materialised, never run."""
    import jax

    from nats_llm_studio_tpu.models import gdn_moe, llama

    return jax.eval_shape(
        lambda: llama.ensure_lm_head(gdn_moe.init_params(mcfg, jax.random.PRNGKey(0))))


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


def _block(t: int, most: int) -> int:
    """The largest power-of-two block up to ``most`` that divides t (t itself
    where none of 8 or more does)."""
    b = most
    while b >= 8:
        if t % b == 0:
            return b
        b //= 2
    return t


def tail_logprobs(params, hf: dict, tokens, n: int, pad_to: tuple[int, int] | None = None,
                  lower: str | None = None) -> np.ndarray:
    """Log-probabilities [n, vocab] of the token after each of the last ``n``
    positions of ``tokens``, float32, from ONE full forward (teacher-forced on
    what was served). ``pad_to`` (T, N): pad the tokens to T and compute N
    rows, one compiled program for every call of a run. ``lower="fp8"`` is
    the CONTROL, never the reference: every matmul's input and the keys and
    values as a cache would hold them rounded to fp8 (e4m3, a scale a row).
    The recurrent state stays in float32, as the configuration states it and
    as a lower-precision serving path would keep it: the control fails by its
    products alone (PERF.md section 2 has the readings, and those with the
    state rounded as well, which read the same)."""
    import jax
    import jax.numpy as jnp

    if lower not in (None, "fp8"):
        raise ValueError(f"unknown lower precision {lower!r}")
    low = _fp8 if lower else (lambda x: x)
    hq, hkv, hd = hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    eps = float(hf["rms_norm_eps"])
    hk, hv = hf["linear_num_key_heads"], hf["linear_num_value_heads"]
    dk, dv, kc = hf["linear_key_head_dim"], hf["linear_value_head_dim"], hf["linear_conv_kernel_dim"]
    chips, rank = share(hf)
    held, top_k = hf["num_experts"], hf["num_experts_per_tok"]
    rot = int(hd * float(hf.get("partial_rotary_factor", 1.0)))
    kinds = layer_kinds(hf)

    t_real = len(tokens)
    t, rows = pad_to or (t_real, n)
    if t_real > t or n > rows or n > t_real:
        raise ValueError(f"{t_real} tokens and {n} rows do not fit pad_to {pad_to}")
    toks = jnp.asarray(list(tokens) + [0] * (t - t_real), jnp.int32)
    start = max(0, t_real - rows)
    blk = _block(t, 512)         # queries and keys attended a block at a time
    tile = min(1024, t)          # rows one expert computes at a time

    freq = float(hf["rope_theta"]) ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(freq, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)

    def rope(x):  # [t, H, D]: the first `rot` dims, (first half, second half) pairs
        x1, x2 = x[..., : rot // 2], x[..., rot // 2: rot]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], axis=-1)

    def attend(q, k, v):
        """q [t, H, D], k / v [t, Hkv, D] -> [t, H, D]: causal softmax, a
        block of queries over a block of keys at a time (a running maximum
        and sum: the same softmax, never [t, t] wide)."""
        g, nb = hq // hkv, t // blk
        qb = q.reshape(nb, blk, hkv, g, hd)
        kb, vb = k.reshape(nb, blk, hkv, hd), v.reshape(nb, blk, hkv, hd)
        at = jnp.arange(blk, dtype=jnp.int32)

        def block(i):
            q_pos = i * blk + at

            def keys(j, carry):
                m, l, acc = carry
                s = jnp.einsum("qhgd,khd->hgqk", qb[i], kb[j]) * hd ** -0.5
                s = jnp.where((j * blk + at)[None, :] <= q_pos[:, None], s, -jnp.inf)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                p = jnp.exp(s - m_new[..., None])
                corr = jnp.exp(m - m_new)
                acc = acc * corr[..., None] + jnp.einsum("hgqk,khd->hgqd", p, vb[j])
                return m_new, l * corr + jnp.sum(p, axis=-1), acc

            init = (jnp.full((hkv, g, blk), -jnp.inf), jnp.zeros((hkv, g, blk)),
                    jnp.zeros((hkv, g, blk, hd)))
            # backwards from the block's own keys: every query sees its own
            # position there, so the maximum is finite from the first block on
            m, l, acc = jax.lax.fori_loop(0, i + 1, lambda n_, c: keys(i - n_, c), init)
            return (acc / l[..., None]).transpose(2, 0, 1, 3).reshape(blk, hq, hd)

        return jax.lax.map(block, jnp.arange(nb, dtype=jnp.int32)).reshape(t, hq, hd)

    def attention(hn, w):
        hl = low(hn)
        qg = hl @ _f32(w["wq"])
        q = rms(qg[:, : hq * hd].reshape(t, hq, hd), w["q_norm"])
        k = rms((hl @ _f32(w["wk"])).reshape(t, hkv, hd), w["k_norm"])
        q, k = low(rope(q)), low(rope(k))  # k: what a cache would hold
        v = low((hl @ _f32(w["wv"])).reshape(t, hkv, hd))
        a = attend(q, k, v).reshape(t, hq * hd) * jax.nn.sigmoid(qg[:, hq * hd:])
        return low(a) @ _f32(w["wo"])

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    def linear(hn, w):
        hl = low(hn)
        qkvz, ba = hl @ _f32(w["w_qkvz"]), hl @ _f32(w["w_ba"])
        c = 2 * hk * dk + hv * dv
        qkv, z = qkvz[:, :c], qkvz[:, c:]
        # depthwise causal convolution: position i sees raw inputs i-K+1 .. i
        padded = jnp.concatenate([jnp.zeros((kc - 1, c), jnp.float32), qkv])
        cw = _f32(w["conv_w"])
        qkv = jax.nn.silu(sum(cw[j] * padded[j: j + t] for j in range(kc)))
        q = l2(qkv[:, : hk * dk].reshape(t, hk, dk)) * dk ** -0.5
        k = l2(qkv[:, hk * dk: 2 * hk * dk].reshape(t, hk, dk))
        v = qkv[:, 2 * hk * dk:].reshape(t, hv, dv)
        q, k = (jnp.repeat(low(z_), hv // hk, axis=1) for z_ in (q, k))
        beta = jax.nn.sigmoid(ba[:, :hv])
        alpha = jnp.exp(-jnp.exp(_f32(w["a_log"])) * jax.nn.softplus(ba[:, hv:] + _f32(w["dt_bias"])))

        def step(s, xs):  # ONE token: the rule as it is written
            qt, kt, vt, at, bt = xs  # [H, dk], [H, dk], [H, dv], [H], [H]
            s = at[:, None, None] * s
            u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", s, kt))
            s = s + kt[:, :, None] * u[:, None, :]
            return s, jnp.einsum("hkv,hk->hv", s, qt)

        _, o = jax.lax.scan(step, jnp.zeros((hv, dk, dv), jnp.float32), (q, k, low(v), alpha, beta))
        y = rms(o, w["gate_norm"]) * jax.nn.silu(z.reshape(t, hv, dv))
        return low(y.reshape(t, hv * dv)) @ _f32(w["w_out"])

    def swiglu(hl, wg, wu, wd):
        return low(jax.nn.silu(hl @ wg) * (hl @ wu)) @ wd

    def experts(x, w, stacks, layer):
        """``w``: the layer's small leaves; ``stacks``: the three WHOLE expert
        stacks [L, held, ., .], read an expert of ``layer`` at a time."""
        hn = rms(x, w["ffn_norm"])
        hl = low(hn)
        p = jax.nn.softmax(hn @ _f32(w["router"]), axis=-1)  # over ALL the experts
        chosen, idx = jax.lax.top_k(p, top_k)
        gate = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
        # a pick's place in the stacks held here; `held` for an absent expert,
        # which sorts last and is computed by no one
        place = jnp.where(idx % chips == rank, idx // chips, held)
        order = jnp.argsort(place.reshape(-1), stable=True)
        pad = jnp.zeros((tile,), jnp.int32)
        row_of = jnp.concatenate([(order // top_k).astype(jnp.int32), pad])
        gate_of = jnp.concatenate([gate.reshape(-1)[order], pad.astype(jnp.float32)])
        count = jnp.sum(jax.nn.one_hot(place.reshape(-1), held, dtype=jnp.int32), axis=0)
        first = jnp.cumsum(count) - count

        def one(e, y):  # ONE expert's float32 copy at a time
            wg, wu, wd = (_f32(jax.lax.dynamic_slice(
                z, (layer, e, 0, 0), (1, 1) + z.shape[2:])[0, 0]) for z in stacks)

            def some(i, y):
                at = first[e] + i * tile
                r = jax.lax.dynamic_slice_in_dim(row_of, at, tile)
                g_ = jax.lax.dynamic_slice_in_dim(gate_of, at, tile)
                g_ = jnp.where(i * tile + jnp.arange(tile) < count[e], g_, 0.0)
                return y.at[r].add(swiglu(hl[r], wg, wu, wd) * g_[:, None])

            return jax.lax.fori_loop(0, (count[e] + tile - 1) // tile, some, y)

        y = jax.lax.fori_loop(0, held, one, jnp.zeros_like(x))
        sg = jax.nn.sigmoid(hn @ _f32(w["shared_gate"]))[:, None]
        return x + y + sg * swiglu(hl, _f32(w["w_gate_s"]), _f32(w["w_up_s"]), _f32(w["w_down_s"]))

    # runs of layers of one kind, each a scan over its slice of that kind's
    # stack (model order; the stacks are what the engine serves); the experts
    # of every layer lie in one stack, model order
    runs, at = [], {"linear": 0, "attention": 0}
    for i, kind in enumerate(kinds):
        if runs and runs[-1][0] == kind:
            runs[-1][3] += 1
        else:
            runs.append([kind, at[kind], i, 1])
        at[kind] += 1

    big = ("w_gate_e", "w_up_e", "w_down_e")

    @jax.jit
    def forward(params, toks, start):
        x = params["embed"][toks].astype(jnp.float32)
        blocks = params["blocks"]

        def take(stack, i):  # layer i's leaves, the expert stacks left whole
            return {k: jax.lax.dynamic_index_in_dim(z, i, axis=0, keepdims=False)
                    for k, z in stack.items() if k not in big}

        for kind, m0, f0, count in runs:
            def layer(x, i, kind=kind, m0=m0, f0=f0):
                w = take(blocks["linear" if kind == "linear" else "attn"], m0 + i)
                mixer = linear if kind == "linear" else attention
                x = x + mixer(rms(x, w["mix_norm"]), w)
                moe = blocks["moe"]
                return experts(x, take(moe, f0 + i), tuple(moe[k] for k in big), f0 + i), None

            x, _ = jax.lax.scan(layer, x, jnp.arange(count, dtype=jnp.int32))
        x = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=0)
        x = low(rms(x, params["out_norm"]))
        return jax.nn.log_softmax(x @ _f32(params["lm_head"]), axis=-1)

    with jax.default_matmul_precision("highest"):
        out = np.asarray(forward(params, toks, jnp.int32(start)), np.float32)
    return out[t_real - n - start: t_real - start]
