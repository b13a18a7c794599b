"""Plain reference for decoders of window-attention layers beside
full-attention layers with other head counts and rotary tables, a gated
attention output, and sigmoid-routed experts beside a shared one after a
leading dense layer, beside the configurations that name it (``"reference":
"swa_gated_moe"``; first: Laguna-XS.2, HF ``laguna``).

A straightforward float32 ``jax.numpy`` forward over a whole prompt and the
tokens served after it: no cache, no ring, no kernel, no batching,
``jax.default_matmul_precision("highest")``, and no import of the program's
model code. It reads the published ``config.json`` keys and the very tree the
engine serves. One compiled program a ``(T, N)``. Two things keep an 18 k
forward cheap enough to run ten times beside the live engine, and neither
changes a number: attention runs in blocks of queries over the blocks of keys
a block can see (none later; in a window layer none that ends more than
``sliding_window`` - 1 back), and an expert layer computes each token's PICKED
experts only, an expert at a time on the rows that picked it (all 256 for
every row would be 30 TFLOP a layer).

The equations (h a token's hidden vector, d wide; layer l of kind k =
``layer_types[l]``, H = ``num_attention_heads_per_layer[l]`` query heads over
``num_key_value_heads`` kv heads of ``head_dim``, consecutive query heads
sharing one):

* h' = RMSNorm(h) gain; q = h' W_q, k = h' W_k, v = h' W_v (no biases). Rotary
  embedding by kind (``rope_parameters[k]``): the first ``partial_rotary_factor``
  x head_dim dims of a head, paired (first half, second half); a ``yarn`` set
  has inv_freq = inter (1 - m) + extra m with extra = theta^(-2i/dims), inter =
  extra / factor, m = 1 - ramp over the correction range of beta_fast,
  beta_slow at ``original_max_position_embeddings``, and cos / sin times
  ``attention_factor``; a ``default`` set is extra alone.
* p = softmax(q k^T / sqrt(head_dim)) over keys j <= i, in a
  ``sliding_attention`` layer also i - j < ``sliding_window``; a = p v.
* ``gating``: g = sigmoid(h' W_g), one number a head; a_head <- g_head a_head;
  h <- h + concat(a) W_o.
* h2 = RMSNorm(h) gain. ``mlp_layer_types[l]`` "dense": h <- h + W_down(silu(h2
  W_gate) * h2 W_up), width ``intermediate_size``. "sparse": s = sigmoid(h2
  W_r); the ``num_experts_per_tok`` experts with the largest s + b are picked;
  w_e = ``moe_routed_scaling_factor`` x s_e / (sum of the picked s + 1e-20); h
  <- h + sum w_e E_e(h2) + E_shared(h2), every expert a SwiGLU of
  ``moe_intermediate_size`` (the shared one of
  ``shared_expert_intermediate_size``). No token is dropped.
* logits = RMSNorm(h) gain W_head (untied).

Assumed where the config gives a key and not a form, each also in the
configuration's ``assumed`` and ONE line here: the gate is per head and a
sigmoid of a projection of the layer's normed input; the router is sigmoid
with a selection bias, the picked scores normalised to 1 then scaled; the
shared expert is added ungated; no q/k norm; a window of ``sliding_window``
keys counts the query's own; rotary pairs are (first half, second half) as
the program's loader lays every family out.

Also here: the mapping from the published keys to the program's
``ModelConfig``, the program's initialiser for the family (``param_shapes``),
and how loud the seeded leaves are drawn (``weight_gains``).
"""

from __future__ import annotations

import math

import numpy as np

# How much louder (or quieter) than N(0, 0.02) the seeded weights draw a leaf
# (by its last name, in every stack). At the published widths (d 2,048):
# - wq / wk x2, NOT ``lib/weights.py``'s x4: this model scales its scores by
#   1/sqrt(128) where Granite scales by 1/128, so at x4 a query and a key of
#   std 3.6 an entry give scores of std 13: every softmax is one-hot, bf16
#   rounding picks another key, and the served path read a decoded median of
#   3.18 against the limit of 1.3 (PERF.md, PR 37, call 1). At x1.5 the
#   served path read 0.12 but the fp8 control only 1.10: UNDER the limit, so
#   the check could not tell bf16 from fp8 (call 2). At x2 (scores of std
#   ~3.2 in a window layer, ~5 in a full one, whose rotary half carries
#   attention_factor^2 = 2) the served path reads 0.31-0.42 and the control
#   2.8-3.0, both a factor of 2-3 from the limit (calls 3 and 4; x2.5 and x3
#   put the served path over it). A key one place outside the window, or a
#   ring written before it is read, still moves the logits by their own
#   spread.
# - wg x2: a gate logit of std 1.8 (x1: 0.9, every gate near 0.5 and a
#   dropped gate a constant factor the next norm removes): gates from 0.1 to
#   0.9 that follow the token, so leaving the gate out moves every head.
# - router x0, e_bias x64: the router's matrix is ZERO, every score is
#   sigmoid(0) = 0.5, and the selection bias alone picks: every token of a
#   layer takes the SAME 8 experts (the 8 largest of the bias's 256 draws,
#   far apart at x64), each weighed 2.5 / 8. Routing is static, by choice:
#   a seed draws the weights, and with a live router the weights drew HOW
#   MANY experts a decode step hits (an expert more a layer is 25 MB more a
#   step, 0.7 % of it). Six seeds spread 0.85 % in tokens/s at e_bias x32
#   (16-17 experts hit of 256), 0.69 % at x64 (12.9), 0.66 % and 0.46 % at
#   x1024 (8.46: a step here and there still hit a ninth), against the 0.5 %
#   a new cell is admitted under; with the router silent every step of
#   every seed hits exactly 8 and twelve seeds spread 0.04 % and 0.13 %
#   (PERF.md section 6, PR 37, calls 1-4; the lesson of PR 33: give every
#   seed the same amount of work). The price: the chip's ``correct`` cannot
#   see a fault in the router's scores (the weights are 2.5 / 8 whatever
#   they are), only in the picks; tests/test_swa_moe.py holds the router to
#   the reference on the CPU with a live router. The hit list, the grouped
#   kernel and the shared expert run as they would for any picks; a
#   balanced router would hit ~85 experts a step here, not 8.
# - w_down_e x0.25: with RANDOM experts a flipped expert is an unrelated
#   vector, so the largest single difference scales with this gain
#   (references/mla_moe_mhc.py, PR 29).
weight_gains = {"wq": 2.0, "wk": 2.0, "wg": 2.0, "router": 0.0, "e_bias": 64.0,
                "w_down_e": 0.25}

KINDS = {"full_attention": "full", "sliding_attention": "window"}


def model_config(hf: dict, max_seq_len: int):
    """Published config.json keys -> the program's ModelConfig."""
    from nats_llm_studio_tpu.models.config import ModelConfig

    kinds = [KINDS[k] for k in hf["layer_types"]]
    heads = hf["num_attention_heads_per_layer"]
    mlp = hf["mlp_layer_types"]
    n = hf["num_hidden_layers"]
    if not (len(kinds) == len(heads) == len(mlp) == n):
        raise ValueError("layer_types, num_attention_heads_per_layer and mlp_layer_types "
                         f"must each name num_hidden_layers = {n} layers")
    n_dense = next((i for i, m in enumerate(mlp) if m != "dense"), n)
    if any(m == "dense" for m in mlp[n_dense:]):
        raise NotImplementedError("dense MLPs after the first sparse layer")
    by_kind = {k: {h for h, kk in zip(heads, kinds) if kk == k} for k in ("full", "window")}
    if any(len(v) != 1 for v in by_kind.values()):
        raise NotImplementedError(f"one head count a layer kind, got {by_kind}")
    rf, rw = hf["rope_parameters"]["full_attention"], hf["rope_parameters"]["sliding_attention"]
    if rw.get("rope_type", "default") != "default":
        raise NotImplementedError("a scaled rotary table in the window layers")
    d = hf["head_dim"]
    yarn = rf.get("rope_type") == "yarn"
    return ModelConfig(
        arch="laguna", vocab_size=hf["vocab_size"], d_model=hf["hidden_size"], n_layers=n,
        n_heads=by_kind["full"].pop(), n_kv_heads=hf["num_key_value_heads"], head_dim=d,
        d_ff=hf["intermediate_size"], rope_theta=float(rf["rope_theta"]),
        rms_eps=float(hf["rms_norm_eps"]), max_seq_len=max_seq_len,
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        n_experts=hf["num_experts"], n_experts_used=hf["num_experts_per_tok"],
        moe_d_ff=hf["moe_intermediate_size"],
        n_shared_experts=hf["shared_expert_intermediate_size"] // hf["moe_intermediate_size"],
        n_dense_layers=n_dense, router_scoring="sigmoid",
        routed_scaling=float(hf["moe_routed_scaling_factor"]),
        rope_factor=float(rf["factor"]) if yarn else 1.0,
        rope_orig_ctx=int(rf.get("original_max_position_embeddings", 0)) if yarn else 0,
        rope_beta_fast=float(rf.get("beta_fast", 32.0)),
        rope_beta_slow=float(rf.get("beta_slow", 1.0)),
        rope_attn_factor=float(rf.get("attention_factor", 1.0)) if yarn else 1.0,
        rope_dim=int(d * float(rf.get("partial_rotary_factor", 1.0))),
        layer_types=tuple(kinds), window=hf["sliding_window"],
        win_n_heads=by_kind["window"].pop(), win_rope_theta=float(rw["rope_theta"]),
        win_rope_dim=int(d * float(rw.get("partial_rotary_factor", 1.0))),
        attn_gate=bool(hf.get("gating", False)), dtype="bfloat16")


def param_shapes(mcfg):
    """The tree the program would load for the family, as shapes: its own
    initialiser with the head materialised, never run."""
    import jax

    from nats_llm_studio_tpu.models import llama, swa_moe

    return jax.eval_shape(
        lambda: llama.ensure_lm_head(swa_moe.init_params(mcfg, jax.random.PRNGKey(0))))


def inv_freq(rp: dict, head_dim: int) -> tuple[np.ndarray, float, int]:
    """(inverse frequencies [dims/2] float64, what cos and sin are multiplied
    by, the rotary dims) of one ``rope_parameters`` set (closed form)."""
    dims = int(head_dim * float(rp.get("partial_rotary_factor", 1.0)))
    theta = float(rp["rope_theta"])
    extra = theta ** (-np.arange(0, dims, 2, dtype=np.float64) / dims)
    if rp.get("rope_type", "default") != "yarn":
        return extra, 1.0, dims
    factor, orig = float(rp["factor"]), rp["original_max_position_embeddings"]

    def corr(rot):  # the dim whose wavelength makes `rot` turns over `orig` positions
        return dims * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr(rp["beta_fast"])), 0)
    high = min(math.ceil(corr(rp["beta_slow"])), dims - 1)
    high = high + 0.001 if low == high else high
    m = 1.0 - np.clip((np.arange(dims // 2) - low) / (high - low), 0.0, 1.0)
    return (extra / factor) * (1.0 - m) + extra * m, float(rp.get("attention_factor", 1.0)), dims


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
    values as a cache would hold them rounded to fp8 (e4m3, a scale a row)."""
    import jax
    import jax.numpy as jnp

    if lower not in (None, "fp8"):
        raise ValueError(f"unknown lower precision {lower!r}")
    low = _fp8 if lower else (lambda x: x)
    d, hkv, hd = hf["hidden_size"], hf["num_key_value_heads"], hf["head_dim"]
    eps = float(hf["rms_norm_eps"])
    n_exp, top_k = hf["num_experts"], hf["num_experts_per_tok"]
    scaling, window = float(hf["moe_routed_scaling_factor"]), int(hf["sliding_window"])
    kinds = [KINDS[k] for k in hf["layer_types"]]
    heads, mlp = hf["num_attention_heads_per_layer"], hf["mlp_layer_types"]
    gated = bool(hf.get("gating", False))

    t_real = len(tokens)
    t, rows = pad_to or (t_real, n)
    if t_real > t or n > rows or n > t_real:
        raise ValueError(f"{t_real} tokens and {n} rows do not fit pad_to {pad_to}")
    toks = jnp.asarray(list(tokens) + [0] * (t - t_real), jnp.int32)
    start = max(0, t_real - rows)
    blk = _block(t, 512)         # queries and keys attended a block at a time
    tile = min(1024, t)          # rows one expert computes at a time

    rope_of = {}
    for name, kind in KINDS.items():
        freq, factor, dims = inv_freq(hf["rope_parameters"][name], hd)
        ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(freq, jnp.float32)
        rope_of[kind] = (jnp.cos(ang) * factor, jnp.sin(ang) * factor, dims)

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)

    def rope(x, kind):  # [t, H, D]: the first `dims` dims, (first half, second half) pairs
        cos, sin, dims = rope_of[kind]
        c, s = cos[:, None, :], sin[:, None, :]
        x1, x2 = x[..., : dims // 2], x[..., dims // 2: dims]
        return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, x[..., dims:]], axis=-1)

    def attend(q, k, v, kind):
        """q [t, H, D], k / v [t, Hkv, D] -> [t, H, D]: softmax over the keys a
        query may see, a block of queries over a block of keys at a time
        (a running maximum and sum: the same softmax, never [t, t] wide)."""
        h = q.shape[1]
        g, nb = h // hkv, t // blk
        qb = q.reshape(nb, blk, hkv, g, hd)
        kb, vb = k.reshape(nb, blk, hkv, hd), v.reshape(nb, blk, hkv, hd)
        at = jnp.arange(blk, dtype=jnp.int32)

        def block(i):
            q_pos = i * blk + at

            def keys(j, carry):
                m, l, acc = carry
                k_pos = j * blk + at
                s = jnp.einsum("qhgd,khd->hgqk", qb[i], kb[j]) * hd ** -0.5
                ok = k_pos[None, :] <= q_pos[:, None]
                if kind == "window":
                    ok = ok & (q_pos[:, None] - k_pos[None, :] < window)
                s = jnp.where(ok, s, -jnp.inf)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                p = jnp.exp(s - m_new[..., None])
                corr = jnp.exp(m - m_new)
                acc = acc * corr[..., None] + jnp.einsum("hgqk,khd->hgqd", p, vb[j])
                return m_new, l * corr + jnp.sum(p, axis=-1), acc

            # the first block of keys any query of block i can see: the one
            # that holds position i * blk - (window - 1)
            first = jnp.maximum(i * blk - (window - 1), 0) // blk if kind == "window" else 0
            init = (jnp.full((hkv, g, blk), -jnp.inf), jnp.zeros((hkv, g, blk)),
                    jnp.zeros((hkv, g, blk, hd)))
            # backwards from the block's own keys: every query sees its own
            # position there, so the maximum is finite before a block that a
            # query cannot see at all (exp(-inf - m) = 0, never inf - inf)
            m, l, acc = jax.lax.fori_loop(
                0, i - first + 1, lambda n_, c: keys(i - n_, c), init)
            return (acc / l[..., None]).transpose(2, 0, 1, 3).reshape(blk, h, hd)

        return jax.lax.map(block, jnp.arange(nb, dtype=jnp.int32)).reshape(t, h, hd)

    def attention(x, w, kind, h):
        hn = rms(x, w["attn_norm"])
        hl = low(hn)
        q = low(rope((hl @ _f32(w["wq"])).reshape(t, h, hd), kind))
        k = low(rope((hl @ _f32(w["wk"])).reshape(t, hkv, hd), kind))  # what a cache would hold
        v = low((hl @ _f32(w["wv"])).reshape(t, hkv, hd))
        a = attend(q, k, v, kind)
        if gated:
            a = a * jax.nn.sigmoid(hl @ _f32(w["wg"]))[..., None]
        return x + low(a.reshape(t, h * hd)) @ _f32(w["wo"])

    def swiglu(hl, wg, wu, wd):
        return low(jax.nn.silu(hl @ wg) * (hl @ wu)) @ wd

    def dense(x, w):
        hl = low(rms(x, w["ffn_norm"]))
        return x + swiglu(hl, _f32(w["w_gate"]), _f32(w["w_up"]), _f32(w["w_down"]))

    def experts(x, w, stacks, layer):
        """``w``: the layer's small leaves; ``stacks``: the three WHOLE expert
        stacks [L, E, ., .], read an expert of ``layer`` at a time."""
        hl = low(rms(x, w["ffn_norm"]))
        sig = jax.nn.sigmoid(hl @ _f32(w["router"]))
        _, idx = jax.lax.top_k(sig + _f32(w["e_bias"]), top_k)
        chosen = jnp.take_along_axis(sig, idx, axis=-1)
        gate = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20) * scaling
        # the (row, pick) pairs by expert; an expert computes the rows that
        # picked it, `tile` at a time (rows past its count add 0 to row 0)
        order = jnp.argsort(idx.reshape(-1), stable=True)
        pad = jnp.zeros((tile,), jnp.int32)
        row_of = jnp.concatenate([(order // top_k).astype(jnp.int32), pad])
        gate_of = jnp.concatenate([gate.reshape(-1)[order], pad.astype(jnp.float32)])
        count = jnp.sum(jax.nn.one_hot(idx.reshape(-1), n_exp, dtype=jnp.int32), axis=0)
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

        y = jax.lax.fori_loop(0, n_exp, one, jnp.zeros_like(x))
        return x + y + swiglu(hl, _f32(w["w_gate_s"]), _f32(w["w_up_s"]), _f32(w["w_down_s"]))

    # runs of layers alike (kind, heads, MLP form), each a scan over its slice
    # of the stacks the engine serves: attention leaves by kind (blocks.full /
    # blocks.win), MLP leaves by form (blocks.dense / blocks.moe), model order
    runs, at = [], {"full": 0, "win": 0, "dense": 0, "moe": 0}
    for kind, h, form in zip(kinds, heads, mlp):
        a, f = ("win" if kind == "window" else "full"), ("dense" if form == "dense" else "moe")
        if runs and runs[-1][:3] == [kind, h, f]:
            runs[-1][5] += 1
        else:
            runs.append([kind, h, f, at[a], at[f], 1])
        at[a] += 1
        at[f] += 1

    big = ("w_gate_e", "w_up_e", "w_down_e")

    @jax.jit
    def forward(params, toks, start):
        x = params["embed"][toks].astype(jnp.float32)
        blocks = params["blocks"]

        def take(stack, i):  # layer i's leaves, the expert stacks left whole
            return {k: jax.lax.dynamic_index_in_dim(z, i, axis=0, keepdims=False)
                    for k, z in stack.items() if k not in big}

        for kind, h, f, a0, f0, count in runs:
            def layer(x, i, kind=kind, h=h, f=f, a0=a0, f0=f0):
                stack = blocks["win" if kind == "window" else "full"]
                x = attention(x, take(stack, a0 + i), kind, h)
                if f == "dense":
                    return dense(x, take(blocks["dense"], f0 + i)), None
                moe = blocks["moe"]
                return experts(x, take(moe, f0 + i), tuple(moe[k] for k in big), f0 + i), None

            x, _ = jax.lax.scan(layer, x, jnp.arange(count, dtype=jnp.int32))
        x = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=0)
        x = low(rms(x, params["out_norm"]))
        return jax.nn.log_softmax(x @ _f32(params["lm_head"]), axis=-1)

    with jax.default_matmul_precision("highest"):
        out = np.asarray(forward(params, toks, jnp.int32(start)), np.float32)
    return out[t_real - n - start: t_real - start]
