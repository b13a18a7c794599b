"""Plain reference for decoders whose every layer is ONE sublayer, a Mamba-2
state-space mixer of several groups, a grouped-query attention layer without
positional embedding, or routed two-matrix relu^2 experts in a latent
(LatentMoE), beside the configurations that name it (``"reference":
"ssm_latent_moe"``; first: NVIDIA-Nemotron-3-Super-120B-A12B, HF
``nemotron_h``).

A straightforward float32 ``jax.numpy`` forward over a whole prompt and the
tokens served after it: a Python loop over the layers, the state-space
recurrence ONE TOKEN AT A TIME in a ``lax.scan`` (never the chunked form: the
chunked form is what it checks), full causal attention a head at a time, the
experts one at a time over the rows that picked them (a gather over the held
ones), no cache, no kernel, no batching,
``jax.default_matmul_precision("highest")``, and no import of the program's
model code. It reads the published ``config.json`` keys and the very tree the
engine serves, a layer at a time (one layer's 128 held experts in float32
would be 2.8 GB; an expert's copy is made when it is computed). One compiled
program a ``(T, N)``.

The equations (h a token's hidden vector; ``norm(x) = x / sqrt(mean(x^2) +
eps) g``, eps ``layer_norm_epsilon``): layer l is the l-th letter of
``hybrid_override_pattern``, h <- h + sublayer_l(norm_l(h)); a final norm; an
untied head.

* ``M`` (Mamba-2), H = ``mamba_num_heads`` heads of P = ``mamba_head_dim``,
  N = ``ssm_state_size``, G = ``n_groups``, K = ``conv_kernel``: [z | xBC | dt]
  = n W_in (H P, H P + 2 G N, H columns); xBC = silu(depthwise causal
  conv_K(xBC) + b); [x | B | C] = xBC, B and C [G, N], head h reads group
  h // (H / G); dt = softplus(dt + dt_bias), no clamp; A = -exp(A_log);
  S[h, p, n] <- exp(dt_h A_h) S[h, p, n] + dt_h x[h, p] B[g(h), n];
  y[h, p] = sum_n C[g(h), n] S[h, p, n] + D_h x[h, p]; y = w * GroupRMSNorm(y
  * silu(z)), each group's H P / G channels normalised alone (the gate BEFORE
  the norm); out = y W_out.
* ``*``: ``num_attention_heads`` queries over ``num_key_value_heads`` kv heads
  of ``head_dim`` (consecutive query heads share one), no bias, NO positional
  embedding, scores x head_dim^-0.5, causal softmax.
* ``E`` (LatentMoE): s = sigmoid(n W_r) over ALL the experts, from the full
  hidden state; picks = the ``num_experts_per_tok`` largest of s + b (b picks,
  it does not weigh; ``n_group`` = ``topk_group`` = 1: no group stage); g =
  s[picks] / (sum s[picks] + 1e-20) x ``routed_scaling_factor``; u = n W_down
  (hidden -> ``moe_latent_size``); r = sum_picks g_e relu(u W1_e)^2 W2_e
  (latent -> ``moe_intermediate_size`` -> latent, no gate matrix); out = r W_up
  (latent -> hidden) + relu(n Ws1)^2 Ws2 (the shared expert, hidden ->
  ``moe_shared_expert_intermediate_size`` -> hidden, on the full hidden state,
  ungated).

**The chip's share** (``expert_parallel`` in the file: ``chips`` share a
layer, this one is ``rank``): ``n_routed_experts`` in the file counts the
experts HELD; the router keeps chips x that many outputs, the picks and their
weights are over all of them, and only a pick e with e mod chips == rank is
computed, on place e // chips of the served stacks. What the absent experts
would have added is left out, as in the program; the latent projections and
the shared expert are computed whole (``share_parts`` gives the two parts, so
that a test can add four ranks' up to the uncut layer).

What the published keys do not spell, each also in the configuration's
``assumed``: no rotary embedding in ``*`` (``rope_theta`` and
``partial_rotary_factor`` are read by nothing in ``nemotron_h``'s attention);
the router and the shared expert at the hidden width while only the routed
experts work in the latent; one pair of latent projections a layer; no clamp
on dt; W_in's columns as two leaves; the served state float32 beside a
bfloat16 model; no multi-token-prediction layer.

Also here: the mapping from the published keys to the program's
``ModelConfig``, the program's initialiser for the family (``param_shapes``),
and how loud the seeded leaves are drawn (``weight_gains``).
"""

from __future__ import annotations

import numpy as np

# How much louder (or quieter) than N(0, 0.02) the seeded weights draw a leaf.
# ``lib/weights.py`` draws EVERY leaf N(0, 0.02 x gain), a_log, dt_bias and
# d_skip too. The state-space leaves take ``references/ssm_hybrid.py``'s gains
# for its reasons (heads with a spread of time constants, so that a stale or
# foreign state, or a wrong group's B or C, moves the logits), but for w_in:
# - dt_bias x100: N(0, 2): dt = softplus from ~0.05 to ~4;
# - a_log x25: N(0, 0.5): A from -0.4 to -2.7; together a head's decay a
#   token runs from 0.95 to 0.02;
# - d_skip x50: N(0, 1): the skip term as loud as in a trained model (D = 1);
# - conv_w x25 and conv_b x10: taps of N(0, 0.5), so the convolution mixes its
#   four inputs (at x1 silu sees ~0 and is linear);
# - w_dt x2: dt varying with the token;
# - w_in at x1 (no gain; that family's file draws it x2): z and xBC of std
#   1.28. The gate silu(z) has a POSITIVE MEAN that every row shares, and it
#   grows with z's spread: at x2 a Mamba-2 layer's outputs of the 64 rows of a
#   step have a cosine of 0.07-0.13 to each other, at x1 0.03-0.04.
# **What the rows of a step share decides how many experts a step streams**
# (the router reads the normed residual; a share c of a logit's variance that
# is one number an expert for EVERY row makes the rows pick alike: 52
# independent rows x 22 picks over 512 experts hit ~115 of the 128 held, c 0.1
# hits 100, c 0.2 hits 88). Read on the chip at these widths (PERF.md section
# 6, PR 55, second session): a sublayer's output RMS a channel and the cosine
# between two rows' outputs are Mamba-2 1.8 and 0.07-0.13 (w_in x2), attention
# 1.6 and 0.21, the routed sum 0.3 and 0.01-0.03, the shared expert 2.95 and
# 0.20-0.27: relu(a)^2 has a positive mean, so 1/6 of the shared expert's
# output energy is ONE vector that every row gets, and at x1 that expert is 70 %
# of the residual's energy. The rows' normed hidden states then share 0.15-0.19
# at the expert layers after the first and a step hits 88 of 128. So:
# - w_down_s x0.5 and w_out x3: the shared expert at an RMS of 1.5, a Mamba-2
#   layer at 5.4 (only the ratios count: every sublayer reads a NORMED
#   residual). The rows share 0.03-0.05 and a step of ~52 live rows hits
#   ~103 of 128. The louder Mamba-2 layers are what keeps the check's readings
#   where they were: a quiet shared expert ALONE (w_down_s x0.25, w_in x1)
#   also hits 105, but the routed sum is then 6 % of the residual's RMS and
#   not 2-4 %, a pick flipped by bfloat16 rounding moves the logits twice as
#   far, and the served path is not ``correct`` (decoded largest 9.8 against
#   8; my chip run). With w_out x2 and w_down_s x0.25 (3.5 %) nine runs were
#   ``correct`` but the window's widest gap came to 4.9 of 6: too near;
# - router x1: logits of std 0.02 x sqrt(4,096) = 1.28, sigmoid scores of std
#   ~0.25: the LIVE router, routing follows the token. At x2 the scores
#   saturate in bfloat16, the selection bias picks among the tied, and 76 are
#   hit where x1 hits 98 (the same weights otherwise);
# - e_bias x1: a selection bias of std 0.02, a tenth of the scores' spread: it
#   decides between near-tied experts and leaves the routing the token's (at
#   x32, the latent-attention family's near-static router, 16 would be hit);
# - w_down_e x0.25: with RANDOM experts a flipped pick is an unrelated vector
#   and the largest single difference scales with this gain
#   (references/mla_moe_mhc.py, PR 29); at top-22 a flip is 1/22 of a row's
#   routed sum where that family's is 1/4;
# - w_lat_down x2: the latent as loud as a normed hidden state's projection
#   needs for relu^2 to see both signs at a spread near 1 (0.02 x 2 x sqrt
#   4,096 = 2.6), so that a swapped projection or silu for relu^2 moves logits.
# wq / wk keep ``lib/weights.py``'s QK_GAIN 4 (peaked attention).
weight_gains = {"dt_bias": 100.0, "a_log": 25.0, "d_skip": 50.0,
                "conv_w": 25.0, "conv_b": 10.0, "w_dt": 2.0, "w_out": 3.0,
                "router": 1.0, "e_bias": 1.0, "w_down_e": 0.25, "w_lat_down": 2.0,
                "w_down_s": 0.5}

KINDS = {"M": "mamba", "E": "experts", "*": "attention"}


def layer_kinds(hf: dict) -> list[str]:
    return [KINDS[c] for c in hf["hybrid_override_pattern"]]


def share(hf: dict) -> tuple[int, int]:
    """(chips that share a layer's experts, this chip's rank)."""
    ep = hf.get("expert_parallel") or {}
    return int(ep.get("chips", 1)), int(ep.get("rank", 0))


def model_config(hf: dict, max_seq_len: int):
    """Published config.json keys -> the program's ModelConfig."""
    from nats_llm_studio_tpu.models.config import ModelConfig

    if len(hf["hybrid_override_pattern"]) != hf["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern does not name num_hidden_layers layers")
    for key, want in (("mlp_hidden_act", "relu2"), ("mamba_hidden_act", "silu"),
                      ("n_group", 1), ("topk_group", 1), ("norm_topk_prob", True),
                      ("use_conv_bias", True), ("attention_bias", False),
                      ("mamba_proj_bias", False), ("mlp_bias", False)):
        if hf.get(key, want) != want:
            raise NotImplementedError(f"{key} = {hf[key]!r}: the family computes {want!r} only")
    chips, rank = share(hf)
    return ModelConfig(
        arch="nemotron_h_moe", vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
        n_layers=hf["num_hidden_layers"], n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"], head_dim=hf["head_dim"],
        d_ff=hf["moe_intermediate_size"], rms_eps=float(hf["layer_norm_epsilon"]),
        max_seq_len=max_seq_len, tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        mlp_act="relu2", n_experts=hf["n_routed_experts"] * chips,
        n_experts_used=hf["num_experts_per_tok"], moe_d_ff=hf["moe_intermediate_size"],
        n_shared_experts=(hf["n_shared_experts"] * hf["moe_shared_expert_intermediate_size"]
                          // hf["moe_intermediate_size"]),
        moe_latent=hf["moe_latent_size"], router_scoring="sigmoid",
        routed_scaling=float(hf["routed_scaling_factor"]), moe_ep_size=chips, moe_ep_rank=rank,
        layer_types=tuple(layer_kinds(hf)), ssm_n_heads=hf["mamba_num_heads"],
        ssm_head_dim=hf["mamba_head_dim"], ssm_d_state=hf["ssm_state_size"],
        ssm_n_groups=hf["n_groups"], ssm_conv=hf["conv_kernel"], ssm_chunk=hf["chunk_size"],
        use_rope=False, dtype="bfloat16")


def param_shapes(mcfg):
    """The tree the program would load for the family, as shapes: its own
    initialiser with the head materialised, never run."""
    import jax

    from nats_llm_studio_tpu.models import llama, ssm_hybrid

    return jax.eval_shape(lambda: llama.ensure_lm_head(
        ssm_hybrid.init_params(mcfg, jax.random.PRNGKey(0))))


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


def _sublayers(hf: dict, t: int, low):
    """{kind: f(normed hidden [t, d], the layer's small leaves, the WHOLE
    expert stacks, the layer's place in them) -> [t, d]} and ``rms``."""
    import jax
    import jax.numpy as jnp

    hq, hkv, hd = hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    eps = float(hf["layer_norm_epsilon"])
    nh, hp, ns, ng = hf["mamba_num_heads"], hf["mamba_head_dim"], hf["ssm_state_size"], hf["n_groups"]
    kc, di = hf["conv_kernel"], hf["mamba_num_heads"] * hf["mamba_head_dim"]
    chips, rank = share(hf)
    held, top_k = hf["n_routed_experts"], hf["num_experts_per_tok"]
    scaling = float(hf["routed_scaling_factor"])
    tile = min(1024, t)  # rows one expert computes at a time
    kv_of = jnp.asarray([h // (hq // hkv) for h in range(hq)], jnp.int32)
    group_of = jnp.asarray([h // (nh // ng) for h in range(nh)], jnp.int32)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)

    def attend(qkv):  # a head at a time: [t, t] scores fit beside the engine
        q, k, v = qkv
        p = jax.nn.softmax(jnp.where(causal, (q @ k.T) * hd ** -0.5, -jnp.inf), axis=-1)
        return p @ v

    def attention(hn, w, *_):
        hl = low(hn)
        q = low((hl @ _f32(w["wq"])).reshape(t, hq, hd))
        k = low((hl @ _f32(w["wk"])).reshape(t, hkv, hd))[:, kv_of]  # what a cache would hold
        v = low((hl @ _f32(w["wv"])).reshape(t, hkv, hd))[:, kv_of]
        a = jax.lax.map(attend, tuple(z.transpose(1, 0, 2) for z in (q, k, v)))
        return low(a.transpose(1, 0, 2).reshape(t, hq * hd)) @ _f32(w["wo"])

    def mamba(hn, w, *_):
        # W_in's columns [z | xBC | dt] are two leaves of the served tree:
        # w_in (z | xBC) and w_dt
        zx, dt = low(hn) @ _f32(w["w_in"]), low(hn) @ _f32(w["w_dt"])
        z, xbc = zx[:, :di], zx[:, di:]
        # depthwise causal convolution: position i sees raw inputs i-K+1 .. i
        padded = jnp.concatenate([jnp.zeros((kc - 1, xbc.shape[1]), jnp.float32), xbc])
        cw = _f32(w["conv_w"])
        xbc = jax.nn.silu(sum(cw[j] * padded[j: j + t] for j in range(kc)) + _f32(w["conv_b"]))
        x = xbc[:, :di].reshape(t, nh, hp)
        bm = xbc[:, di: di + ng * ns].reshape(t, ng, ns)[:, group_of]  # [t, H, N]: a head's group
        cm = xbc[:, di + ng * ns:].reshape(t, ng, ns)[:, group_of]
        dt = jax.nn.softplus(dt + _f32(w["dt_bias"]))
        a = -jnp.exp(_f32(w["a_log"]))

        def step(s, xs):  # ONE token: the recurrence as it is written
            xt, dtt, bt, ct = xs  # [H, P], [H], [H, N], [H, N]
            s = jnp.exp(dtt * a)[:, None, None] * s + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
            return s, jnp.einsum("hpn,hn->hp", s, ct)

        _, y = jax.lax.scan(step, jnp.zeros((nh, hp, ns), jnp.float32), (low(x), dt, low(bm), low(cm)))
        y = (y + _f32(w["d_skip"])[:, None] * x).reshape(t, di) * jax.nn.silu(z)
        y = y.reshape(t, ng, di // ng)  # each group's channels normalised alone
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
        return low(y.reshape(t, di) * _f32(w["gate_norm"])) @ _f32(w["w_out"])

    def relu2(x):
        return jnp.square(jnp.maximum(x, 0.0))

    def routed(hn, w, stacks, layer):
        """This chip's routed sum, in the latent, back at the hidden width."""
        s = jax.nn.sigmoid(hn @ _f32(w["router"]))  # over ALL the experts
        _, idx = jax.lax.top_k(s + _f32(w["e_bias"]), top_k)
        chosen = jnp.take_along_axis(s, idx, axis=-1)
        gate = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20) * scaling
        u = low(low(hn) @ _f32(w["w_lat_down"]))
        # a pick's place in the stacks held here; `held` for an absent expert,
        # which sorts last and is computed by no one
        place = jnp.where(idx % chips == rank, idx // chips, held)
        order = jnp.argsort(place.reshape(-1), stable=True)
        pad = jnp.zeros((tile,), jnp.int32)
        row_of = jnp.concatenate([(order // top_k).astype(jnp.int32), pad])
        gate_of = jnp.concatenate([gate.reshape(-1)[order], pad.astype(jnp.float32)])
        count = jnp.sum(jax.nn.one_hot(place.reshape(-1), held, dtype=jnp.int32), axis=0)
        first = jnp.cumsum(count) - count

        def one(e, r):  # ONE expert's float32 copy at a time
            w1, w2 = (_f32(jax.lax.dynamic_slice(
                z, (layer, e, 0, 0), (1, 1) + z.shape[2:])[0, 0]) for z in stacks)

            def some(i, r):
                at = first[e] + i * tile
                rows = jax.lax.dynamic_slice_in_dim(row_of, at, tile)
                g_ = jax.lax.dynamic_slice_in_dim(gate_of, at, tile)
                g_ = jnp.where(i * tile + jnp.arange(tile) < count[e], g_, 0.0)
                return r.at[rows].add((low(relu2(u[rows] @ w1)) @ w2) * g_[:, None])

            return jax.lax.fori_loop(0, (count[e] + tile - 1) // tile, some, r)

        r = jax.lax.fori_loop(0, held, one, jnp.zeros_like(u))
        return low(r) @ _f32(w["w_lat_up"])

    def shared(hn, w):
        return low(relu2(low(hn) @ _f32(w["w_up_s"]))) @ _f32(w["w_down_s"])

    def experts(hn, w, stacks, layer):
        return routed(hn, w, stacks, layer) + shared(hn, w)

    return {"mamba": mamba, "attention": attention, "experts": experts,
            "routed": routed, "shared": shared}, rms


BIG = ("w_up_e", "w_down_e")
STACK = {"mamba": "mamba", "attention": "attn", "experts": "moe"}


def _take(stack, i):
    """Layer i's leaves, the expert stacks left whole."""
    return {k: z[i] for k, z in stack.items() if k not in BIG}


def share_parts(params, hf: dict, hn, place: int, lower: str | None = None):
    """(this chip's routed sum, the shared expert's output) [t, d] float32 of
    the ``E`` layer at ``place`` of ``blocks.moe`` over NORMED rows ``hn``
    [t, d]: the ranks' first parts and ONE second part add up to the uncut
    layer."""
    import jax
    import jax.numpy as jnp

    f, _ = _sublayers(hf, hn.shape[0], _fp8 if lower else (lambda x: x))
    moe = params["blocks"]["moe"]
    with jax.default_matmul_precision("highest"):
        w, hn = _take(moe, place), jnp.asarray(hn, jnp.float32)
        return (np.asarray(f["routed"](hn, w, tuple(moe[k] for k in BIG), place)),
                np.asarray(f["shared"](hn, w)))


def tail_logprobs(params, hf: dict, tokens, n: int, pad_to: tuple[int, int] | None = None,
                  lower: str | None = None) -> np.ndarray:
    """Log-probabilities [n, vocab] of the token after each of the last ``n``
    positions of ``tokens``, float32, from ONE full forward (teacher-forced on
    what was served). ``pad_to`` (T, N): pad the tokens to T and compute N
    rows, one compiled program for every call of a run. ``lower="fp8"`` is
    the CONTROL, never the reference: every matmul's input, the latent the
    experts read and the keys and values as a cache would hold them rounded to
    fp8 (e4m3, a scale a row). The recurrent state stays in float32, as the
    configuration states it and as a lower-precision serving path would keep
    it: the control fails by its products alone."""
    import jax
    import jax.numpy as jnp

    if lower not in (None, "fp8"):
        raise ValueError(f"unknown lower precision {lower!r}")
    low = _fp8 if lower else (lambda x: x)
    kinds = layer_kinds(hf)
    t_real = len(tokens)
    t, rows = pad_to or (t_real, n)
    if t_real > t or n > rows or n > t_real:
        raise ValueError(f"{t_real} tokens and {n} rows do not fit pad_to {pad_to}")
    toks = jnp.asarray(list(tokens) + [0] * (t - t_real), jnp.int32)
    start = max(0, t_real - rows)
    f, rms = _sublayers(hf, t, low)

    @jax.jit
    def forward(params, toks, start):
        x = params["embed"][toks].astype(jnp.float32)
        blocks, at = params["blocks"], {kind: 0 for kind in STACK}
        stacks = tuple(blocks["moe"][k] for k in BIG) if "moe" in blocks else None
        for kind in kinds:  # model order; each kind's stack in its own order
            w = _take(blocks[STACK[kind]], at[kind])
            x = x + f[kind](rms(x, w["mix_norm"]), w, stacks, at[kind])
            at[kind] += 1
        x = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=0)
        x = low(rms(x, params["out_norm"]))
        return jax.nn.log_softmax(x @ _f32(params["lm_head"]), axis=-1)

    with jax.default_matmul_precision("highest"):
        out = np.asarray(forward(params, toks, jnp.int32(start)), np.float32)
    return out[t_real - n - start: t_real - start]
