"""Plain reference for decoders of state-space (Mamba-2) layers beside
grouped-query attention layers without positional embedding, beside the
configurations that name it (``"reference": "ssm_hybrid"``; first:
granite-4.0-h-micro, HF ``GraniteMoeHybrid`` with no routed experts).

A straightforward float32 ``jax.numpy`` forward over a whole prompt and the
tokens served after it: the state-space recurrence ONE TOKEN AT A TIME in a
``lax.scan`` (never the chunked form: the chunked form is what it checks), full
causal attention a head at a time, no cache, no kernel, no batching,
``jax.default_matmul_precision("highest")``, and no import of the program's
model code. It reads the published ``config.json`` keys and the very tree the
engine serves, a layer at a time. One compiled program a ``(T, N)``.

The equations (h a token's hidden vector, d wide):

* Block, both kinds: r = h; h = RMSNorm(h); h = mixer(h); h = r +
  ``residual_multiplier`` h; r = h; h = RMSNorm(h); h = W_down(silu(W_gate h) *
  W_up h), width ``shared_intermediate_size`` (``num_local_experts`` 0: the
  shared MLP is the whole MLP); h = r + ``residual_multiplier`` h. Embedding x
  ``embedding_multiplier``, logits / ``logits_scaling``.
* Attention (``layer_types[i] == "attention"``): ``num_attention_heads``
  queries over ``num_key_value_heads`` kv heads (consecutive query heads share
  one), NO rotary embedding (``position_embedding_type`` "nope"), scores x
  ``attention_multiplier``, causal softmax.
* Mamba-2 (``"mamba"``), H = ``mamba_n_heads`` heads of P = ``mamba_d_head``,
  N = ``mamba_d_state``, G = ``mamba_n_groups`` = 1, K = ``mamba_d_conv``:
  [z | xBC | dt] = W_in h (d_inner, d_inner + 2 G N, H columns); xBC =
  silu(depthwise causal conv_K(xBC) + b); [x | B | C] = xBC; dt = softplus(dt +
  dt_bias); A = -exp(A_log); S[h, p, n] <- exp(dt_h A_h) S[h, p, n] + dt_h
  x[h, p] B[n]; y[h, p] = sum_n C[n] S[h, p, n] + D_h x[h, p]; y = RMSNorm(y *
  silu(z)) w over all of d_inner (the gate BEFORE the norm); out = W_out y.

Departures from the published model, each also in the configuration's
``assumed``: the head is drawn on its own where the published model ties it to
the embedding (``lib/weights.py``: a random tied head repeats one byte for
ever); the served state is float32 beside a bfloat16 model.

Also here: the mapping from the published keys to the program's
``ModelConfig``, the program's initialiser for the family (``param_shapes``),
and how loud the seeded leaves are drawn (``weight_gains``).
"""

from __future__ import annotations

import numpy as np

# How much louder (or quieter) than N(0, 0.02) the seeded weights draw a leaf.
# ``lib/weights.py`` draws EVERY leaf N(0, 0.02 x gain), a_log, dt_bias and
# d_skip too (in a trained model they are A in [1, 16], dt in [1e-3, 1e-1],
# D = 1). At gain 1 every head has A = -exp(~0) = -1 and dt = softplus(~0) =
# 0.69: a decay of 0.5 a token, a state that forgets in two or three tokens,
# and a stale or foreign state moves no logit. The gains below spread the
# heads' time constants:
# - dt_bias x100: N(0, 2): dt = softplus(N(0, 2) + w_in's dt column) from
#   ~0.05 to ~4, so with A near -1 a head's decay a token runs from 0.95
#   (a memory of ~20-50 tokens) to 0.02;
# - a_log x25: N(0, 0.5): A from -0.4 to -2.7, widening the same spread;
# - d_skip x50: N(0, 1): the skip term as loud as in a trained model (D = 1);
# - conv_w x25 and conv_b x10: taps of N(0, 0.5), so the convolution mixes its
#   four inputs (at x1 silu sees ~0 and is linear, and a tail off by one moves
#   nothing);
# - w_in x2: B, C and x loud enough for the state's read-out to matter beside
#   the skip term; w_dt x2: dt's own columns vary dt with the token.
# wq / wk keep ``lib/weights.py``'s QK_GAIN 4 (peaked attention).
weight_gains = {"dt_bias": 100.0, "a_log": 25.0, "d_skip": 50.0,
                "conv_w": 25.0, "conv_b": 10.0, "w_in": 2.0, "w_dt": 2.0}


def model_config(hf: dict, max_seq_len: int):
    """Published config.json keys -> the program's ModelConfig."""
    from nats_llm_studio_tpu.models.config import ModelConfig

    if hf.get("num_local_experts"):
        raise NotImplementedError("routed experts beside state-space layers")
    return ModelConfig(
        arch="granitehybrid",
        vocab_size=hf["vocab_size"],
        d_model=hf["hidden_size"],
        n_layers=hf["num_hidden_layers"],
        n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"],
        head_dim=hf["hidden_size"] // hf["num_attention_heads"],
        d_ff=hf["shared_intermediate_size"],
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rms_eps=float(hf["rms_norm_eps"]),
        max_seq_len=max_seq_len,
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        embedding_scale=float(hf.get("embedding_multiplier", 1.0)),
        residual_scale=float(hf.get("residual_multiplier", 1.0)),
        attention_scale=float(hf["attention_multiplier"]),
        logit_scale=1.0 / float(hf.get("logits_scaling", 1.0)),
        dtype="bfloat16",
        layer_types=tuple(hf["layer_types"]),
        ssm_n_heads=hf["mamba_n_heads"],
        ssm_head_dim=hf["mamba_d_head"],
        ssm_d_state=hf["mamba_d_state"],
        ssm_n_groups=hf["mamba_n_groups"],
        ssm_conv=hf["mamba_d_conv"],
        ssm_chunk=hf["mamba_chunk_size"],
        use_rope=hf.get("position_embedding_type", "rope") == "rope",
    )


def param_shapes(mcfg):
    """The tree the program would load: its own initialiser for the family
    with the head materialised, never run."""
    import jax

    from nats_llm_studio_tpu.models import llama, ssm_hybrid

    return jax.eval_shape(lambda: llama.ensure_lm_head(
        ssm_hybrid.init_params(mcfg, jax.random.PRNGKey(0))))


FP8_MAX = 448.0  # float8_e4m3fn's largest finite value


def _fp8(x):
    """``x`` as an fp8 (e4m3) path would hold it: each row scaled to the
    format's range by its own largest entry, rounded, scaled back."""
    import jax.numpy as jnp

    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / FP8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _f32(w):
    """One leaf as float32: a plain array, or the served tree's int8 leaf
    (codes ``q`` times per-output-channel scale ``s``)."""
    import jax.numpy as jnp

    if hasattr(w, "q") and hasattr(w, "s"):
        return w.q.astype(jnp.float32) * w.s.astype(jnp.float32)
    return w.astype(jnp.float32)


def tail_logprobs(params, hf: dict, tokens, n: int, pad_to: tuple[int, int] | None = None,
                  lower: str | None = None) -> np.ndarray:
    """Log-probabilities [n, vocab] of the token after each of the last ``n``
    positions of ``tokens``, float32, from ONE full forward (teacher-forced on
    what was served). ``pad_to`` (T, N): the tokens are padded to T and N rows
    computed, one compiled program a (T, N); the padding lies behind the last
    token, and a position sees only those before it.

    ``lower="fp8"`` is the CONTROL, never the reference: the same forward with
    every matmul's input, the keys and values, and the STATE as it is carried
    from token to token rounded to fp8 (e4m3, a scale a row)."""
    import jax
    import jax.numpy as jnp

    if lower not in (None, "fp8"):
        raise ValueError(f"unknown lower precision {lower!r}")
    low = _fp8 if lower else (lambda x: x)
    d, hq, hkv = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = d // hq
    eps = float(hf["rms_norm_eps"])
    emb_x = float(hf.get("embedding_multiplier", 1.0))
    res_x = float(hf.get("residual_multiplier", 1.0))
    att_x = float(hf["attention_multiplier"])
    logit_div = float(hf.get("logits_scaling", 1.0))
    nh, hp, ns = hf["mamba_n_heads"], hf["mamba_d_head"], hf["mamba_d_state"]
    kc, di = hf["mamba_d_conv"], hf["mamba_n_heads"] * hf["mamba_d_head"]
    if hf["mamba_n_groups"] != 1:
        raise NotImplementedError("the reference is written for one group of B and C")
    if hf.get("position_embedding_type", "rope") != "nope":
        raise NotImplementedError("the reference is written for NoPE attention")
    kinds = list(hf["layer_types"])
    t_real = len(tokens)
    t, rows = pad_to or (t_real, n)
    if t_real > t or n > rows or n > t_real:
        raise ValueError(f"{t_real} tokens and {n} rows do not fit pad_to {pad_to}")
    toks = jnp.asarray(list(tokens) + [0] * (t - t_real), jnp.int32)
    start = max(0, t_real - rows)
    kv_of = jnp.asarray([h // (hq // hkv) for h in range(hq)], jnp.int32)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)

    def attend(qkv):  # a head at a time: [t, t] scores fit beside the engine
        q, k, v = qkv
        p = jax.nn.softmax(jnp.where(causal, (q @ k.T) * att_x, -jnp.inf), axis=-1)
        return p @ v

    def attention(h, w):
        hl = low(h)
        q = low((hl @ _f32(w["wq"])).reshape(t, hq, hd))
        k = low((hl @ _f32(w["wk"])).reshape(t, hkv, hd))[:, kv_of]
        v = low((hl @ _f32(w["wv"])).reshape(t, hkv, hd))[:, kv_of]
        a = jax.lax.map(attend, tuple(z.transpose(1, 0, 2) for z in (q, k, v)))
        return low(a.transpose(1, 0, 2).reshape(t, hq * hd)) @ _f32(w["wo"])

    def mamba(h, w):
        # W_in's columns [z | xBC | dt] are two leaves of the served tree:
        # w_in (z | xBC) and w_dt
        zx, dt = low(h) @ _f32(w["w_in"]), low(h) @ _f32(w["w_dt"])
        z, xbc = zx[:, :di], zx[:, di:]
        # depthwise causal convolution: position i sees raw inputs i-K+1 .. i
        padded = jnp.concatenate([jnp.zeros((kc - 1, xbc.shape[1]), jnp.float32), xbc])
        cw = _f32(w["conv_w"])
        xbc = jax.nn.silu(sum(cw[j] * padded[j: j + t] for j in range(kc)) + _f32(w["conv_b"]))
        x = xbc[:, :di].reshape(t, nh, hp)
        bm, cm = xbc[:, di: di + ns], xbc[:, di + ns:]
        dt = jax.nn.softplus(dt + _f32(w["dt_bias"]))
        a = -jnp.exp(_f32(w["a_log"]))

        def step(s, xs):  # ONE token: the recurrence as it is written
            xt, dtt, bt, ct = xs  # [H, P], [H], [N], [N]
            s = jnp.exp(dtt * a)[:, None, None] * s + (dtt[:, None] * xt)[:, :, None] * bt
            s = low(s.reshape(nh * hp, ns)).reshape(nh, hp, ns)  # what a pool would hold
            return s, jnp.einsum("hpn,n->hp", s, ct)

        _, y = jax.lax.scan(step, jnp.zeros((nh, hp, ns), jnp.float32),
                            (low(x.reshape(t, di)).reshape(t, nh, hp), dt, low(bm), low(cm)))
        y = y + _f32(w["d_skip"])[:, None] * x
        y = rms(y.reshape(t, di) * jax.nn.silu(z), w["gate_norm"])
        return low(y) @ _f32(w["w_out"])

    def block(mixer):
        def f(x, w):
            x = x + mixer(rms(x, w["mix_norm"]), w) * res_x
            hl = low(rms(x, w["ffn_norm"]))
            ffn = low(jax.nn.silu(hl @ _f32(w["w_gate"])) * (hl @ _f32(w["w_up"]))) @ _f32(
                w["w_down"])
            return x + ffn * res_x
        return f

    # runs of layers of one kind, each a scan over its slice of that kind's
    # stack (model order; the stacks are what the engine serves)
    runs, at = [], {"mamba": 0, "attention": 0}
    for kind in kinds:
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, at[kind], 1])
        at[kind] += 1

    @jax.jit
    def forward(params, toks, start):
        x = params["embed"][toks].astype(jnp.float32) * emb_x
        for kind, first, count in runs:
            stack = params["blocks"]["mamba" if kind == "mamba" else "attn"]
            part = jax.tree.map(lambda a: a[first: first + count], stack)
            f = block(mamba if kind == "mamba" else attention)
            x, _ = jax.lax.scan(lambda x, w: (f(x, w), None), x, part)
        x = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=0)
        x = low(rms(x, params["out_norm"]))
        head = params.get("lm_head")
        head = _f32(params["embed"]).T if head is None else _f32(head)
        return jax.nn.log_softmax((x @ head) / logit_div, axis=-1)

    with jax.default_matmul_precision("highest"):
        out = np.asarray(forward(params, toks, jnp.int32(start)), np.float32)
    return out[t_real - n - start: t_real - start]
