"""Plain reference for decoders of Lightning linear-attention layers beside
block-sparse attention layers with a dense SwiGLU in every layer and muP
scalings, beside the configurations that name it (``"reference": "sala"``;
first: MiniCPM-SALA, HF ``minicpm_sala``).

A straightforward float32 ``jax.numpy`` forward over a whole prompt and the
tokens served after it: the linear recurrence ONE TOKEN AT A TIME in a
``lax.scan`` (never the chunked form: the chunked form is what it checks), the
sparse layer as its equations say a query (pooled keys, the group's summed
softmax, a block's best pooled key, the forced blocks, the top-k, softmax over
the keys of the picked blocks), a block of queries at a time over ALL the keys,
no cache, no kernel, no chunk, ``jax.default_matmul_precision("highest")``,
and no import of the program's model code. It reads the published
``config.json`` keys and the very tree the engine serves, a layer at a time.
One compiled program a ``(T, N)``.

The equations (h a token's hidden vector; ``norm(x; g) = x / sqrt(mean(x^2) +
eps) g``):

* x_0 = ``scale_emb`` E[token]. Layer l: x <- x + s Mixer(norm(x)); x <- x +
  s SwiGLU(norm(x)), s = ``scale_depth`` / sqrt(PUBLISHED ``num_hidden_layers``
  ) (``published.num_hidden_layers`` where the file cuts the depth). Logits:
  norm(x_L) / (``hidden_size`` / ``dim_model_base``) W_head, untied.
* ``lightning-attn`` (H = ``lightning_nh`` heads of d = ``lightning_head_dim``):
  q = h W_q, k = h W_k, v = h W_v, gamma = h W_g; q, k: norm over a head
  (gains q_norm, k_norm), rotary over all d dims at ``rope_theta``, (first
  half, second half) pairs; q <- q d^-0.5. A head with S [d, d], S_0 = 0:
  S_t = lambda S_{t-1} + k_t v_t^T, o_t = S_t^T q_t, lambda = exp(-a), a =
  sigmoid(the leaf ``decay``) a (layer, head). y = (norm(concat_h o_t; g_o) *
  sigmoid(gamma)) W_o.
* ``minicpm4`` (``num_attention_heads`` query heads over ``num_key_value_heads``
  kv heads of ``head_dim``, group G; sizes from ``sparse_config``): [q | gamma]
  = h W_q, k = h W_k, v = h W_v; q, k: norm over a head; NO rotary. A query at
  position t sees n = t + 1 keys. n <= ``dense_len``: causal softmax attention
  at D^-0.5. Otherwise, a kv head: (1) pooled keys c_j = mean(k_{stride j} ..
  k_{stride j + kernel - 1}) for every j with stride j + kernel <= n; (2) p_gj
  = softmax_j(q_g . c_j D^-0.5) a query head, r_j = sum_g p_gj; (3) block b
  (keys block b .. block b + block - 1) scores R_b = max of r_j over the j
  whose keys meet the block; (4) R_b = +inf for b < ``init_blocks`` and for
  every block that meets the last ``window_size`` keys; (5) P = the ``topk``
  blocks of largest R_b among 0 .. floor(t / block), ties to the lower index;
  (6) softmax over the keys s <= t in a block of P. out = (attn *
  sigmoid(gamma)) W_o.

Departures from the published model, each also in the configuration's
``assumed``: ``sparse_config`` and the decay table are the family's convention
(the catalog's copy of the config holds neither); the rates are read from a
leaf as logits; the selection is the paper's single-stage form; plain or
sparse attention is decided by the QUERY's own n, the one causal reading of a
switch the published code makes by the length of a call; the in-projection of
a sparse layer lies plainly as [q | gate].

Also here: the mapping from the published keys to the program's
``ModelConfig``, the program's initialiser for the family (``param_shapes``),
and how loud the seeded leaves are drawn (``weight_gains``).
"""

from __future__ import annotations

import numpy as np

# How much louder than N(0, 0.02) the seeded weights draw a leaf.
# ``lib/weights.py`` draws EVERY float leaf N(0, 0.02 x gain) but the rank-1
# ``*norm`` gains, which are ones.
# - decay x150: the leaf holds the rates' LOGITS, N(0, 3): a = sigmoid from
#   0.002 to 0.998, lambda a token from 0.998 (a memory of ~500 tokens, so a
#   carry that is wrong across a chunk's or an admit's edge moves the logits)
#   to 0.37. At x1 every head would have a = 0.5.
# - wq / wk keep ``lib/weights.py``'s QK_GAIN 4, which the q / k norms of both
#   mixers cancel: with gains of one a score q . k d^-0.5 has a spread of 1
#   whatever the projections' loudness (the sparse layers' wq also makes the
#   output gate, which x4 puts well away from 0.5). So the sparse layers'
#   softmax is as flat as a unit-gain q/k norm makes it, here and in any
#   configuration with such norms; what makes a wrong pick visible is
# - blocks.attn.wv x8: the values of the sparse layers loud, so that what the
#   picked blocks hold is a visible share of the residual stream (~5 % a
#   layer) and picking others moves the logits. The CPU tests draw the norm
#   gains loud as well (peaked scores), which the harness's rule for ``*norm``
#   leaves cannot.
# - wg x2: the lightning layers' output gates vary from 0.1 to 0.9.
weight_gains = {"decay": 150.0, "blocks.attn.wv": 8.0, "wg": 2.0}


def layer_kinds(hf: dict) -> list[str]:
    names = {"minicpm4": "sparse", "lightning-attn": "lightning"}
    kinds = [names[m] for m in hf["mixer_types"]]
    if len(kinds) != hf["num_hidden_layers"]:
        raise ValueError(f"mixer_types names {len(kinds)} layers of {hf['num_hidden_layers']}")
    return kinds


def published_depth(hf: dict) -> int:
    return int((hf.get("published") or {}).get("num_hidden_layers", hf["num_hidden_layers"]))


def model_config(hf: dict, max_seq_len: int):
    """Published config.json keys -> the program's ModelConfig."""
    from nats_llm_studio_tpu.models.config import ModelConfig

    for key, want in (("qk_norm", True), ("use_output_gate", True), ("use_output_norm", True),
                      ("attn_use_output_gate", True), ("attn_use_rope", False),
                      ("lightning_use_rope", True), ("attention_bias", False)):
        if bool(hf.get(key, want)) != want:
            raise NotImplementedError(f"{key} = {hf.get(key)!r}")
    if hf["lightning_nkv"] != hf["lightning_nh"] or hf["lightning_scale"] != "1/sqrt(d)":
        raise NotImplementedError("lightning key heads that are shared, or another scale")
    sc = hf["sparse_config"]
    return ModelConfig(
        arch="minicpm_sala", vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
        n_layers=hf["num_hidden_layers"], n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"], head_dim=hf["head_dim"],
        d_ff=hf["intermediate_size"], rope_theta=float(hf["rope_theta"]),
        rms_eps=float(hf["rms_norm_eps"]), max_seq_len=max_seq_len,
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        embedding_scale=float(hf["scale_emb"]),
        residual_scale=float(hf["scale_depth"]) / published_depth(hf) ** 0.5,
        logit_scale=float(hf["dim_model_base"]) / hf["hidden_size"],
        layer_types=tuple(layer_kinds(hf)),
        lin_k_heads=hf["lightning_nkv"], lin_v_heads=hf["lightning_nh"],
        lin_k_dim=hf["lightning_head_dim"], lin_v_dim=hf["lightning_head_dim"],
        attn_out_gate=True, qk_norm=True, use_rope=False,
        sparse_kernel=sc["kernel_size"], sparse_stride=sc["kernel_stride"],
        sparse_block=sc["block_size"], sparse_window=sc["window_size"],
        sparse_init_blocks=sc["init_blocks"], sparse_topk=sc["topk"],
        sparse_dense_len=sc["dense_len"],
        stage_first_layer=int(hf.get("stage_first_layer", 0)), stage_depth=published_depth(hf),
        dtype="bfloat16")


def param_shapes(mcfg):
    """The tree the program would load for the family, as shapes: its own
    initialiser with the head materialised, never run."""
    import jax

    from nats_llm_studio_tpu.models import llama, sala

    return jax.eval_shape(
        lambda: llama.ensure_lm_head(sala.init_params(mcfg, jax.random.PRNGKey(0))))


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
    the CONTROL, never the reference: every matmul's input, the keys and
    values as a cache would hold them and the pooled keys rounded to fp8
    (e4m3, a scale a row). The recurrent state stays in float32, as the
    configuration states it."""
    import jax
    import jax.numpy as jnp

    if lower not in (None, "fp8"):
        raise ValueError(f"unknown lower precision {lower!r}")
    low = _fp8 if lower else (lambda x: x)
    hq, hkv, hd = hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    lh, ld = hf["lightning_nh"], hf["lightning_head_dim"]
    eps = float(hf["rms_norm_eps"])
    sc = hf["sparse_config"]
    kernel, stride, block = sc["kernel_size"], sc["kernel_stride"], sc["block_size"]
    window, init_blocks, topk, dense_len = (
        sc["window_size"], sc["init_blocks"], sc["topk"], sc["dense_len"])
    res = float(hf["scale_depth"]) / published_depth(hf) ** 0.5
    kinds = layer_kinds(hf)

    t_real = len(tokens)
    t, rows = pad_to or (t_real, n)
    if t_real > t or n > rows or n > t_real:
        raise ValueError(f"{t_real} tokens and {n} rows do not fit pad_to {pad_to}")
    toks = jnp.asarray(list(tokens) + [0] * (t - t_real), jnp.int32)
    start = max(0, t_real - rows)
    qblk = _block(t, 128)        # queries of a sparse layer attended at a time
    tile = _block(t, 2048)       # rows the SwiGLU computes at a time
    head_tile = _block(rows, 1024)
    n_pooled = max((t - kernel) // stride + 1, 0)
    n_blocks = -(-t // block)

    freq = float(hf["rope_theta"]) ** (-np.arange(0, ld, 2, dtype=np.float64) / ld)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(freq, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)

    def rope(x):  # [t, H, d]: all the dims, (first half, second half) pairs
        x1, x2 = x[..., : ld // 2], x[..., ld // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    def lightning(hn, w):
        hl = low(hn)
        q = rope(rms((hl @ _f32(w["wq"])).reshape(t, lh, ld), w["q_norm"])) * ld ** -0.5
        k = rope(rms((hl @ _f32(w["wk"])).reshape(t, lh, ld), w["k_norm"]))
        v = (hl @ _f32(w["wv"])).reshape(t, lh, ld)
        gamma = hl @ _f32(w["wg"])
        lam = jnp.exp(-jax.nn.sigmoid(_f32(w["decay"])))[:, None, None]  # [H, 1, 1]

        def step(s, xs):  # ONE token: the rule as it is written
            qt, kt, vt = xs  # [H, d] each
            s = lam * s + kt[:, :, None] * vt[:, None, :]
            return s, jnp.einsum("hkv,hk->hv", s, qt)

        _, o = jax.lax.scan(step, jnp.zeros((lh, ld, ld), jnp.float32), (low(q), low(k), low(v)))
        y = rms(o.reshape(t, lh * ld), w["out_norm"]) * jax.nn.sigmoid(gamma)
        return low(y) @ _f32(w["wo"])

    # which pooled keys meet which block, as the overlap of their key ranges
    pj = np.arange(max(n_pooled, 1))[:, None]
    bb = np.arange(n_blocks)[None, :]
    meets = jnp.asarray((pj * stride < (bb + 1) * block) & (pj * stride + kernel > bb * block))

    def sparse(hn, w):
        hl = low(hn)
        g = hq // hkv
        qg = hl @ _f32(w["wq"])
        q = low(rms(qg[:, : hq * hd].reshape(t, hq, hd), w["q_norm"]))
        k = low(rms((hl @ _f32(w["wk"])).reshape(t, hkv, hd), w["k_norm"]))  # what a cache holds
        v = low((hl @ _f32(w["wv"])).reshape(t, hkv, hd))
        # (1) every pooled key of the sequence; a query reads those that exist for it
        if n_pooled:
            at = (np.arange(n_pooled) * stride)[:, None] + np.arange(kernel)[None, :]
            pooled = low(jnp.mean(k[at], axis=1))  # [NP, Hkv, D]
        qb = q.reshape(t // qblk, qblk, hkv, g, hd)
        key_pos = jnp.arange(t, dtype=jnp.int32)
        key_block = key_pos // block

        def queries(i):
            pos = i * qblk + jnp.arange(qblk, dtype=jnp.int32)  # [Q]
            seen = pos + 1
            allowed = jnp.ones((qblk, hkv, t), bool)
            if n_pooled and t > dense_len:
                last_key = jnp.arange(n_pooled) * stride + kernel
                exists = last_key[None, :] <= seen[:, None]  # [Q, NP]
                s = jnp.einsum("qhgd,jhd->qhgj", qb[i], pooled) * hd ** -0.5
                p = jax.nn.softmax(jnp.where(exists[:, None, None, :], s, -jnp.inf), axis=-1)
                p = jnp.where(exists[:, None, None, :], p, 0.0)  # a query with no pooled key yet
                r = jnp.sum(p, axis=2)  # (2) [Q, Hkv, NP]
                r = jnp.where(exists[:, None, :], r, -jnp.inf)
                score = jnp.max(jnp.where(meets[None, None], r[..., None], -jnp.inf), axis=2)  # (3)
                score = jnp.maximum(score, 0.0)  # a block none of whose pooled keys exists yet
                blk = jnp.arange(n_blocks, dtype=jnp.int32)[None, :]
                forced = (blk < init_blocks) | ((blk + 1) * block > (seen - window)[:, None])  # (4)
                score = jnp.where(forced[:, None, :], jnp.inf, score)
                score = jnp.where((blk <= (pos // block)[:, None])[:, None, :], score, -jnp.inf)
                picks = jax.lax.top_k(score, min(topk, n_blocks))[1]  # (5) [Q, Hkv, topk]
                picked = jnp.any(picks[..., None] == jnp.arange(n_blocks), axis=2)  # [Q, Hkv, NB]
                allowed = jnp.take(picked, key_block, axis=2) | (seen <= dense_len)[:, None, None]
            allowed = allowed & (key_pos[None, :] <= pos[:, None])[:, None, :]
            s = jnp.einsum("qhgd,khd->qhgk", qb[i], k) * hd ** -0.5
            p = jax.nn.softmax(jnp.where(allowed[:, :, None, :], s, -jnp.inf), axis=-1)  # (6)
            return jnp.einsum("qhgk,khd->qhgd", p, v).reshape(qblk, hq * hd)

        a = jax.lax.map(queries, jnp.arange(t // qblk, dtype=jnp.int32)).reshape(t, hq * hd)
        return low(a * jax.nn.sigmoid(qg[:, hq * hd:])) @ _f32(w["wo"])

    def swiglu(x, w):
        wg, wu, wd = _f32(w["w_gate"]), _f32(w["w_up"]), _f32(w["w_down"])

        def some(xs):
            hl = low(rms(xs, w["ffn_norm"]))
            return low(jax.nn.silu(hl @ wg) * (hl @ wu)) @ wd

        return jax.lax.map(some, x.reshape(t // tile, tile, -1)).reshape(x.shape)

    # runs of layers of one kind, each a scan over its slice of that kind's
    # stack (model order; the stacks are what the engine serves)
    runs, at = [], {"lightning": 0, "sparse": 0}
    for kind in kinds:
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, at[kind], 1])
        at[kind] += 1

    @jax.jit
    def forward(params, toks, start):
        x = params["embed"][toks].astype(jnp.float32) * float(hf["scale_emb"])
        blocks = params["blocks"]
        for kind, m0, count in runs:
            stack = blocks["linear" if kind == "lightning" else "attn"]
            mixer = lightning if kind == "lightning" else sparse

            def layer(x, i, stack=stack, mixer=mixer, m0=m0):
                w = {k: jax.lax.dynamic_index_in_dim(z, m0 + i, axis=0, keepdims=False)
                     for k, z in stack.items()}
                x = x + res * mixer(rms(x, w["mix_norm"]), w)
                return x + res * swiglu(x, w), None

            x, _ = jax.lax.scan(layer, x, jnp.arange(count, dtype=jnp.int32))
        x = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=0)
        x = low(rms(x, params["out_norm"])) * (float(hf["dim_model_base"]) / hf["hidden_size"])
        head = _f32(params["lm_head"])
        out = jax.lax.map(lambda xs: jax.nn.log_softmax(xs @ head, axis=-1),
                          x.reshape(rows // head_tile, head_tile, -1))
        return out.reshape(rows, -1)

    with jax.default_matmul_precision("highest"):
        out = np.asarray(forward(params, toks, jnp.int32(start)), np.float32)
    return out[t_real - n - start: t_real - start]
