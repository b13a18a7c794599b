"""Latent-attention (MLA) decoder with sigmoid-routed experts and a
multi-stream (mHC) residual, beside ``models/llama.py``.

``models.llama.forward`` / ``forward_decode_paged`` / ``make_cache`` hand a
config with ``cfg.is_mla`` to the twins here, so the batcher, the pool, the
table and the sampling are the ones every other family uses. What differs:

* **Two stacks.** ``blocks.dense`` (the leading ``n_dense_layers``, SwiGLU of
  width ``d_ff``) and ``blocks.moe`` (routed experts of width ``moe_d_ff``
  beside ``n_shared_experts`` always-on ones), each one ``lax.scan``; a layer's
  cache index is its place in the whole model.
* **The cache pair is (latent, rotary key)**, one "kv head" each:
  ``[B, L, 1, S, kv_lora_rank]`` holds the latent AFTER its norm and
  ``[B, L, 1, S, qk_rope_head_dim]`` (rows zero-padded to whole 128-lane
  tiles, as the device lays them out anyway) the shared key AFTER rotation —
  576 numbers a token a layer where GQA would hold 2 x Hkv x D. ``ops/kvcache.py``
  and the batcher treat the pair as they treat K and V.
* **Attention.** T > ``_ABSORB_MAX_T`` (prefill, chunks): latents are
  expanded to per-head keys and values (qk 192 / v 128 at the published
  widths) and attended in XLA, queries in blocks, KEYS IN BLOCKS too: a loop
  over the row cache whose trip count is the furthest block a query of the
  call can see, so a chunk costs what its live prefix costs and no plane
  over the program's whole window exists. T small (decode, the draft
  bundle of a verify): absorbed — ``W_uk`` folds into the query, ``W_uv`` into
  the output, and the scores read the latents themselves
  (``ops/mla_attention.py``: a Pallas kernel over the pool and the table, or
  the XLA form over a gathered view).
* **Experts are dropless**, in one of three forms. A call with fewer (row,
  expert) picks than the layer has experts (a decode step: 8 rows x top-4
  under 64) lists on the device the experts its live rows hit and reads
  those alone, each once (the hit list). A call with more (a prefill chunk,
  a chunk group, a verify bundle) sorts its picks by expert and computes each
  on its own expert only (grouped). Both index the whole stacks by (layer,
  expert): ``ops/moe_experts.py``. Quantised expert stacks and a mesh of
  several chips are dense dispatch: every expert computes every row,
  weighted by the gate (0 for experts a row did not pick), a group of
  experts at a time so the [rows, experts, width] intermediates stay small.
  ``expert_path`` chooses, from shapes and leaf types alone.
  ``moe_capacity_factor`` is not read. The decode path also counts, per
  layer, the distinct experts the live rows hit (the list's length: what the
  step streams) and the most rows on one expert.
* **The residual is n streams** (``hc_mult``): ``X <- H_res X + H_post^T f(norm(H_pre X))``
  with the three maps made from the streams themselves, ``H_res`` projected
  onto doubly stochastic matrices by Sinkhorn rounds (rows first). At
  ``hc_mult == 1`` it is the plain residual ``h + f(norm(h))``: one stream in
  ``cfg.dtype``, no mixer leaves, no ``mix`` scope.
* **The query** goes through a rank-``q_lora_rank`` pair with a norm between
  (``w_dq``, ``q_norm``, ``w_uq``), or at ``q_lora_rank == 0`` through one
  matrix ``wq [d, H x (nope + rope)]``. Both choices are the configuration's,
  made once in Python like ``expert_path``: a tree holds the leaves of its
  form only.

Norms, the router, softmax and the stream mixers run in float32; the small
float32 products (router, mixers) at ``Precision.HIGHEST``.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import sinkhorn
from ..ops.kvcache import kv_pool_write_rows, kv_update_slice
from ..ops.layers import apply_rope, rms_norm, swiglu, yarn_frequencies
from ..ops.wquant import flat_rows, mm
from .config import ModelConfig
from .experts import expert_path, moe_ffn, split_stacks

Params = dict[str, Any]

_HI = jax.lax.Precision.HIGHEST
# query widths up to this take the absorbed form (decode is 1, a speculative
# verify k+1); anything wider is a prefill and expands the window's latents
_ABSORB_MAX_T = 16
# queries attended together in the expanded form
_Q_BLOCK = 512
# keys expanded and scored together by ``blocked_attention``: [B, H, block of
# queries, _K_BLOCK] f32 scores, 34 MB for a group of four at 256 queries. Small
# enough that the scores of a block do not travel through HBM four times: a
# group of four at 12k keys took 65 ms at 1,024, 40 ms at 512, 39 ms at 256
# (PERF.md, PR 44)
_K_BLOCK = 256


# ---------------------------------------------------------------------------
# rotary embedding with YaRN
# ---------------------------------------------------------------------------


def yarn_inv_freq(cfg: ModelConfig) -> np.ndarray:
    """Inverse frequencies [rope/2] of the rotary part (``ops.layers.
    yarn_frequencies`` over this family's fields)."""
    return yarn_frequencies(cfg.qk_rope_head_dim, cfg.rope_theta, cfg.rope_factor,
                            cfg.rope_orig_ctx, cfg.rope_beta_fast, cfg.rope_beta_slow)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 or not mscale else 0.1 * mscale * math.log(factor) + 1.0


def rope_tables(cfg: ModelConfig, positions: jax.Array) -> tuple[jax.Array, jax.Array]:
    """cos/sin [..., rope/2] f32 at ``positions`` (YaRN frequencies; the
    tables carry mscale(factor, mscale) / mscale(factor, mscale_all_dim))."""
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(yarn_inv_freq(cfg))
    m = _yarn_mscale(cfg.rope_factor, cfg.rope_mscale) / _yarn_mscale(
        cfg.rope_factor, cfg.rope_mscale_all_dim)
    return jnp.cos(ang) * m, jnp.sin(ang) * m


# ---------------------------------------------------------------------------
# the multi-stream residual
# ---------------------------------------------------------------------------


def hc_maps(X: jax.Array, w: jax.Array, a: jax.Array, b: jax.Array, cfg: ModelConfig):
    """The three maps of one mixer from the streams X [n, B, T, d]: ``pre``
    and ``post`` [n, B, T], ``res`` [n, n, B, T] (row i = what new stream i
    takes of each old stream). The stream axes lead, so nothing is laid out
    4 wide and a row or column sum adds whole [B, T] planes. The Sinkhorn
    rounds are one kernel (``ops/sinkhorn.py``): as an XLA loop they are 6
    launches a round, 1,680 a pass through 7 layers, which was 60 % of the
    operations of a prefill chunk and of the events a device trace of it
    holds (PERF.md, PR 32; PR 30 for decode). Only a call of more rows than
    the kernel's one block holds keeps the ``fori_loop`` (unrolled the rounds
    are 20 x the program text of every mixer, and half a minute of compile
    each)."""
    n = cfg.hc_mult
    xf = X.astype(jnp.float32)
    rrms = jax.lax.rsqrt(jnp.mean(xf * xf, axis=(0, 3), keepdims=True) + cfg.rms_eps)
    wf = w.astype(jnp.float32).reshape(n, X.shape[-1], -1)
    z = jnp.einsum("nbtd,ndk->kbt", xf * rrms, wf, precision=_HI)
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)[:, None, None]
    pre = jax.nn.sigmoid(af[0] * z[:n] + bf[:n])
    post = 2.0 * jax.nn.sigmoid(af[1] * z[n: 2 * n] + bf[n: 2 * n])
    res = jnp.exp(jnp.clip(af[2] * z[2 * n:] + bf[2 * n:],
                           cfg.hc_res_clamp_min, cfg.hc_res_clamp_max))
    res = res.reshape((n, n) + res.shape[1:])

    def round_(_, r):  # rows first
        r = r / (jnp.sum(r, axis=1, keepdims=True) + cfg.hc_eps)
        return r / (jnp.sum(r, axis=0, keepdims=True) + cfg.hc_eps)

    rows = X.shape[1] * X.shape[2]
    if rows <= sinkhorn.MAX_ROWS:
        res = sinkhorn.sinkhorn_rounds(
            res.reshape(n, n, rows), cfg.hc_sinkhorn_iters, cfg.hc_eps,
            interpret=jax.default_backend() != "tpu").reshape(res.shape)
    else:
        res = jax.lax.fori_loop(0, cfg.hc_sinkhorn_iters, round_, res)
    return pre, post, res


def hc_read(X: jax.Array, pre: jax.Array) -> jax.Array:
    """u = H_pre X: the one stream a sublayer reads, [B, T, d] float32."""
    return jnp.sum(pre[..., None] * X.astype(jnp.float32), axis=0)


def hc_write(X: jax.Array, post: jax.Array, res: jax.Array, y: jax.Array) -> jax.Array:
    """X <- H_res X + H_post^T y."""
    xf, yf = X.astype(jnp.float32), y.astype(jnp.float32)
    mixed = jnp.sum(res[..., None] * xf[None], axis=1)  # [n(i), B, T, d]
    return (mixed + post[..., None] * yf[None]).astype(X.dtype)


def _residual(X, p, which: str, cfg: ModelConfig, f):
    """One sublayer ("attn" or "ffn") around the streams: y = f(RMSNorm(H_pre
    X)). ``f`` may return (y, aux); aux is passed through. The maps, the read
    and the write are the ``mix`` scope; the norm and ``f`` the sublayer's
    (``seq/mla``, or ``ffn`` with ``f``'s own word inside it). One stream
    (``hc_mult`` 1) is the plain residual: X [B, T, d] in ``cfg.dtype``."""
    if cfg.hc_mult == 1:
        with jax.named_scope("seq/mla" if which == "attn" else "ffn"):
            out = f(rms_norm(X, p[f"{which}_norm"], cfg.rms_eps))
            y, aux = out if isinstance(out, tuple) else (out, None)
            return X + y.astype(X.dtype), aux
    with jax.named_scope("mix"):
        pre, post, res = hc_maps(X, p[f"hc_{which}_w"], p[f"hc_{which}_a"],
                                 p[f"hc_{which}_b"], cfg)
        u = hc_read(X, pre)
    with jax.named_scope("seq/mla" if which == "attn" else "ffn"):
        u = rms_norm(u, p[f"{which}_norm"].astype(jnp.float32), cfg.rms_eps)
        out = f(u.astype(jnp.dtype(cfg.dtype)))
    y, aux = out if isinstance(out, tuple) else (out, None)
    with jax.named_scope("mix"):
        return hc_write(X, post, res, y), aux


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def mla_project(h: jax.Array, p: Params, cfg: ModelConfig, cos, sin):
    """q_nope [B,T,H,dn], q_rope [B,T,H,dr] (rotated), the normalised latent
    c [B,T,R] and the rotated shared key kr [B,T,dr]: what the cache holds is
    exactly (c, kr)."""
    b, t, _ = h.shape
    dn, dr, r = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    if cfg.q_lora_rank:
        q = mm(rms_norm(mm(h, p["w_dq"]), p["q_norm"], cfg.rms_eps), p["w_uq"])
    else:
        q = mm(h, p["wq"])
    q = flat_rows(q).reshape(b, t, cfg.n_heads, dn + dr)
    q_rope = apply_rope(q[..., dn:], cos, sin)
    ckr = mm(h, p["w_dkv"])
    c = rms_norm(ckr[..., :r], p["kv_norm"], cfg.rms_eps)
    kr = apply_rope(ckr[:, :, None, r:], cos, sin)[:, :, 0]
    return q[..., :dn], q_rope, c, kr


def _lane_padded(x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """A rotary key (or query) zero-padded to the rotary cache's row width
    (``cfg.kv_cache_dims``): zeros add nothing to a score."""
    pad = cfg.kv_cache_dims()[1][1] - x.shape[-1]
    return x if pad == 0 else jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _w_ukv(p: Params, cfg: ModelConfig):
    """W_ukv as (W_uk [R, H, dn], W_uv [R, H, dv])."""
    w = p["w_ukv"].reshape(cfg.kv_lora_rank, cfg.n_heads, -1)
    return w[..., : cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def blocked_attention(q_nope, q_rope, c_all, r_all, layer, win: int, p, cfg: ModelConfig,
                      positions: jax.Array) -> jax.Array:
    """Attention of T queries over the first ``win`` tokens of layer
    ``layer`` of the row caches ``c_all`` / ``r_all`` [B, L, 1, S, .] in the
    EXPANDED form: every latent becomes a key [H, dn] (+ the shared rotary
    key) and a value [H, dv]; ``positions`` [B, T]: query t sees keys at
    index <= positions[b, t]. A block of keys at a time: a block's latents
    are expanded, scored and folded into a running maximum, sum and output,
    and the loop stops at the furthest block a query of the call can see
    (``max(positions) // block``). So the work follows the live prefix, not
    ``win``, and nothing [B, H, T, win] exists. Rows of a group with
    different starts share the trip count; a row's blocks past its own
    frontier are masked whole and add nothing (block 0 holds key 0, which
    every query sees, so the running maximum is finite from the first block
    on). Returns [B, T, H*dv]. ``tests/test_mla_moe_plain.py`` holds it to
    the one-plane definition and to the absorbed form."""
    b, t, hq, _ = q_nope.shape
    w_uk, w_uv = _w_ukv(p, cfg)
    dr, dv = cfg.qk_rope_head_dim, cfg.v_head_dim
    kb = math.gcd(win, _K_BLOCK)
    kb = kb if kb >= min(128, _K_BLOCK) else win  # an odd window: one block
    zero = jnp.zeros((), jnp.int32)
    layer = jnp.asarray(layer, jnp.int32)

    def keys(cache, at):  # [B, kb, W] of the layer's rows at..at+kb
        sl = jax.lax.dynamic_slice(cache, (zero, layer, zero, at, zero),
                                   (b, 1, 1, kb, cache.shape[-1]))
        return sl[:, 0, 0].astype(q_nope.dtype)

    def block(qn, qr, pos):  # [B, t', H, .], [B, t'] -> [B, t', H, dv]
        tq = qn.shape[1]
        q = jnp.concatenate([qn, qr], axis=-1)

        def fold(j, carry):
            m, l, acc = carry
            at = j * kb
            c_blk, kr_blk = keys(c_all, at), keys(r_all, at)[..., :dr]
            k_nope = jnp.einsum("bsr,rhd->bshd", c_blk, w_uk)
            v = jnp.einsum("bsr,rhd->bshd", c_blk, w_uv)
            # a head's whole key [nope | the shared rotary key], so that ONE
            # product writes the float32 scores once: two products wrote the
            # plane, read it back and wrote it again (91 -> 65 ms a group of
            # four at 12k keys; PERF.md, PR 44)
            k = jnp.concatenate([k_nope, jnp.broadcast_to(
                kr_blk[:, :, None, :], k_nope.shape[:3] + (dr,))], axis=-1)
            s = jnp.einsum("bthd,bshd->bhts", q, k, preferred_element_type=jnp.float32)
            key_pos = at + jnp.arange(kb, dtype=jnp.int32)
            s = jnp.where((key_pos[None, None, :] <= pos[:, :, None])[:, None],
                          s * cfg.attn_scale, jnp.float32(-1e30))
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            pr = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(pr, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhts,bshd->bhtd", pr.astype(v.dtype), v, preferred_element_type=jnp.float32)
            return m_new, l, acc

        n_blocks = jnp.minimum(jnp.max(pos) // kb + 1, win // kb).astype(jnp.int32)
        m0 = jnp.full((b, hq, tq), -1e30, jnp.float32)
        _, l, acc = jax.lax.fori_loop(
            0, n_blocks, fold, (m0, jnp.zeros_like(m0), jnp.zeros((b, hq, tq, dv), jnp.float32)))
        return jnp.swapaxes(acc / l[..., None], 1, 2).astype(q_nope.dtype)

    if t <= _Q_BLOCK or t % _Q_BLOCK:
        out = block(q_nope, q_rope, positions)
    else:
        def split(x):  # [B, T, ...] -> [T/blk, B, blk, ...]
            return jnp.moveaxis(x.reshape((b, t // _Q_BLOCK, _Q_BLOCK) + x.shape[2:]), 1, 0)

        out = jax.lax.map(lambda a: block(*a), (split(q_nope), split(q_rope), split(positions)))
        out = jnp.moveaxis(out, 0, 1).reshape(b, t, hq, -1)
    return out.reshape(b, t, -1)


def absorbed_queries(q_nope: jax.Array, p: Params, cfg: ModelConfig) -> jax.Array:
    """q~_h = W_uk_h q_nope_h: [B, T, H, R], the query in latent space."""
    return jnp.einsum("bthd,rhd->bthr", q_nope, _w_ukv(p, cfg)[0])


def absorbed_output(o_lat: jax.Array, p: Params, cfg: ModelConfig) -> jax.Array:
    """o_h = W_uv_h^T (sum_s p_s c_s): [B, T, H, R] -> [B, T, H*dv]."""
    o = jnp.einsum("bthr,rhd->bthd", o_lat, _w_ukv(p, cfg)[1])
    return o.reshape(o.shape[0], o.shape[1], -1)


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------


def _stacks(params: Params, cfg: ModelConfig):
    """((stack leaves, first layer index, ffn kind), ...) in model order."""
    out = []
    if cfg.n_dense_layers:
        out.append((params["blocks"]["dense"], 0, "dense"))
    if cfg.n_layers > cfg.n_dense_layers:
        out.append((params["blocks"]["moe"], cfg.n_dense_layers, "moe"))
    return out


def _embed(params: Params, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    """The embedding copied into all n streams: [n, B, T, d] float32. The
    streams stay float32 from here to the head (the mixers read and write
    them in float32 anyway): carried in bf16 they would be rounded 14 times a
    token in 7 layers, and that noise is what flips a token's 4th and 5th
    expert against the reference. A sublayer's input is cast to ``cfg.dtype``
    after its norm, so every product runs as in the other families. One
    stream is [B, T, d] in ``cfg.dtype``, as in the other families."""
    with jax.named_scope("embed"):
        if cfg.hc_mult == 1:
            return params["embed"][tokens].astype(jnp.dtype(cfg.dtype)) * cfg.embedding_scale
        x = params["embed"][tokens].astype(jnp.float32) * cfg.embedding_scale
        return jnp.broadcast_to(x[None], (cfg.hc_mult,) + x.shape)


def _head(params: Params, cfg: ModelConfig, X, logit_positions, t: int) -> jax.Array:
    """The final norm and head read the SUM of the streams."""
    from .llama import lm_head_logits

    if cfg.hc_mult == 1:
        return lm_head_logits(params, cfg, X, logit_positions, t)
    with jax.named_scope("head/logits"):
        x = jnp.sum(X, axis=0).astype(jnp.dtype(cfg.dtype))
    return lm_head_logits(params, cfg, x, logit_positions, t)


def _mlp(h, p: Params, cfg: ModelConfig):
    with jax.named_scope("mlp"):
        return swiglu(h, p["w_gate"], p["w_up"], p["w_down"], cfg.mlp_act)


def _layers(params: Params, cfg: ModelConfig, X, caches, attention, live=None, mesh=None):
    """Both stacks in model order, each one scan: attention then FFN around
    the streams. ``attention(h, p, caches, layer) -> (out, caches)`` is the
    caller's (row caches or pools). Returns (X, caches, the expert layers'
    counters [n_moe_layers, 3] or None without ``live``).

    Where the expert layers take the hit list or the grouped form
    (``expert_path``), the three expert stacks leave the scan's ``xs`` and
    are closed over WHOLE, the scan carrying the layer's place in them: a
    scan's slice of a stack handed to a kernel or a loop as an operand would
    be copied, 1.4 GB a layer."""
    stats = None
    rows = X.shape[-3] * X.shape[-2]
    for stack, first, kind in _stacks(params, cfg):
        form, whole = "dense", None
        if kind == "moe":
            form = expert_path(cfg, rows, stack, mesh)
        if form != "dense":
            whole, stack = split_stacks(stack)

        def block(carry, inputs, kind=kind, form=form, whole=whole):
            X, caches = carry
            p, layer, place = inputs
            X, caches = _residual(X, p, "attn", cfg, lambda h: attention(h, p, caches, layer))
            if kind == "dense":
                X, st = _residual(X, p, "ffn", cfg, lambda h: _mlp(h, p, cfg))
            else:
                X, st = _residual(X, p, "ffn", cfg, lambda h: moe_ffn(
                    h, p, cfg, live, form, whole, place))
            return (X, caches), st

        place = jnp.arange(jax.tree.leaves(stack)[0].shape[0], dtype=jnp.int32)
        (X, caches), st = jax.lax.scan(block, (X, caches), (stack, first + place, place))
        if kind == "moe":
            stats = st
    return X, caches, stats


def forward(
    params: Params, cfg: ModelConfig, tokens: jax.Array,
    k_cache: jax.Array,  # latents [B, L, 1, S, R]
    v_cache: jax.Array,  # rotary keys [B, L, 1, S, dr]
    start_pos: jax.Array, attn_window: int | None = None, mesh=None,
    ring_slot=None, logit_positions=None, fresh_prefill: bool = False,
    uniform_start: bool = False,
):
    """``models.llama.forward``'s contract over row caches of latents
    (positional layout only: the family is served on the paged pool, whose
    programs pass no ``ring_slot``)."""
    if ring_slot is not None:
        raise NotImplementedError(
            "latent-attention models are served on the paged pool (KV_PAGED=1): "
            "the shared-ring cache layout has no latent form")
    del fresh_prefill, uniform_start  # one attention path for every start
    b, t = tokens.shape
    s_max = k_cache.shape[3]
    win = attn_window if (attn_window is not None and attn_window < s_max) else s_max
    positions = start_pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    with jax.named_scope("seq/mla"):
        cos, sin = rope_tables(cfg, positions)
    zero = jnp.zeros((), jnp.int32)
    X = _embed(params, cfg, tokens)

    def attention(h, p, caches, layer):
        c_all, r_all = caches
        q_nope, q_rope, c, kr = mla_project(h, p, cfg, cos, sin)

        def write(cache_b, rows_b, s):  # [L, 1, S, W] <- [T, W] at (layer, 0, s)
            return kv_update_slice(cache_b, rows_b[None, None], (layer, zero, s, zero))

        c_all = jax.vmap(write)(c_all, c, start_pos)
        r_all = jax.vmap(write)(r_all, _lane_padded(kr, cfg), start_pos)

        def window(cache):
            w = cache.shape[-1]
            sl = jax.lax.dynamic_slice(cache, (zero, layer, zero, zero, zero), (b, 1, 1, win, w))
            return sl[:, 0, 0].astype(h.dtype)

        if t <= _ABSORB_MAX_T:
            from ..ops.mla_attention import mla_absorbed_attention

            o = absorbed_output(mla_absorbed_attention(
                absorbed_queries(q_nope, p, cfg), q_rope, window(c_all),
                window(r_all)[..., : cfg.qk_rope_head_dim], positions, cfg.attn_scale), p, cfg)
        else:
            o = blocked_attention(q_nope, q_rope, c_all, r_all, layer, win, p, cfg, positions)
        return mm(o, p["wo"]), (c_all, r_all)

    X, caches, _ = _layers(params, cfg, X, (k_cache, v_cache), attention, mesh=mesh)
    return _head(params, cfg, X, logit_positions, t), caches[0], caches[1]


def forward_decode_paged(
    params: Params, cfg: ModelConfig, tokens: jax.Array,
    k_pool, v_pool,  # latents [NB, L, 1, T, R], rotary keys [NB, L, 1, T, dr]
    tbl: jax.Array, start_pos: jax.Array, mesh=None,
):
    """``models.llama.forward_decode_paged``'s contract: W tokens a slot,
    written into the pool and then attended over the slot's whole table by
    the absorbed Pallas kernel. Returns (logits, k_pool, v_pool, moe_stats):
    ``moe_stats`` int32 [n_moe_layers, 3] counts, over the slots that hold a
    request (table entry 0 is a real block), the distinct experts hit, the
    most rows on one expert and the live rows in each expert layer."""
    from ..ops.mla_attention import mla_paged_decode_attention_auto
    from ..ops.ssm_scan import live_slots

    b, w = tokens.shape
    positions = start_pos[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
    with jax.named_scope("seq/mla"):
        cos, sin = rope_tables(cfg, positions)
    live = tbl[:, 0] > 0
    listed = live_slots(live)  # once a launch: every layer's kernel walks the same list
    X = _embed(params, cfg, tokens)

    def attention(h, p, pools, layer):
        cp, rp = pools
        q_nope, q_rope, c, kr = mla_project(h, p, cfg, cos, sin)
        cp = kv_pool_write_rows(cp, c[:, :, None], tbl, start_pos, layer)
        rp = kv_pool_write_rows(rp, _lane_padded(kr, cfg)[:, :, None], tbl, start_pos, layer)
        o_lat = mla_paged_decode_attention_auto(
            absorbed_queries(q_nope, p, cfg), _lane_padded(q_rope, cfg), cp, rp, tbl,
            start_pos, listed, layer, cfg.attn_scale)
        return mm(absorbed_output(o_lat, p, cfg), p["wo"]), (cp, rp)

    X, pools, stats = _layers(params, cfg, X, (k_pool, v_pool), attention,
                              live.astype(jnp.float32), mesh)
    if stats is None:
        stats = jnp.zeros((0, 3), jnp.int32)
    return _head(params, cfg, X, None, w), pools[0], pools[1], stats


def make_cache(cfg: ModelConfig, batch: int, seq_len: int | None = None,
               dtype: str | None = None):
    """Zeroed (latent, rotary key) row caches [B, L, 1, S, .]."""
    if cfg.kv_quant == "int8":
        raise NotImplementedError(
            "TPU_KV_QUANT=int8 is not implemented for latent-attention models: "
            "the latent is both key and value, and one scale a row cannot serve both")
    s = seq_len or cfg.max_seq_len
    dt = jnp.dtype(dtype or cfg.dtype)
    return tuple(jnp.zeros((batch, cfg.n_layers, h, s, w), dt)
                 for h, w in cfg.kv_cache_dims())


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Random small-scale init; the tree is what a loader of the family would
    build (``benchmark/references/mla_moe_mhc.py param_shapes`` names it):
    the mixers' leaves only with more than one stream, the query's pair or its
    one matrix by ``q_lora_rank``."""
    dt = jnp.dtype(cfg.dtype)
    keys = iter(jax.random.split(key, 64))

    def rand(*shape):
        return (jax.random.normal(next(keys), shape, jnp.float32) * 0.02).astype(dt)

    d, hq, n = cfg.d_model, cfg.n_heads, cfg.hc_mult
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rq, rkv, maps = cfg.q_lora_rank, cfg.kv_lora_rank, n * n + 2 * n

    def stack(L: int, ffn: dict) -> Params:
        out: Params = {"attn_norm": jnp.ones((L, d), dt), "ffn_norm": jnp.ones((L, d), dt),
                       "kv_norm": jnp.ones((L, rkv), dt)}
        if rq:  # the query through a low-rank pair, or one matrix
            out |= {"q_norm": jnp.ones((L, rq), dt),
                    "w_dq": rand(L, d, rq), "w_uq": rand(L, rq, hq * (dn + dr))}
        else:
            out["wq"] = rand(L, d, hq * (dn + dr))
        out |= {"w_dkv": rand(L, d, rkv + dr), "w_ukv": rand(L, rkv, hq * (dn + dv)),
                "wo": rand(L, hq * dv, d)}
        if n > 1:  # one stream has no mixers
            for which in ("attn", "ffn"):
                out |= {f"hc_{which}_w": rand(L, n * d, maps), f"hc_{which}_a": rand(L, 3),
                        f"hc_{which}_b": rand(L, maps)}
        return out | ffn

    blocks: Params = {}
    ld, lm = cfg.n_dense_layers, cfg.n_layers - cfg.n_dense_layers
    if ld:
        ff = cfg.d_ff
        blocks["dense"] = stack(ld, {
            "w_gate": rand(ld, d, ff), "w_up": rand(ld, d, ff), "w_down": rand(ld, ff, d)})
    if lm:
        e, fe, fs = cfg.n_experts, cfg.moe_d_ff, cfg.n_shared_experts * cfg.moe_d_ff
        blocks["moe"] = stack(lm, {
            "router": rand(lm, d, e), "e_bias": rand(lm, e),
            "w_gate_e": rand(lm, e, d, fe), "w_up_e": rand(lm, e, d, fe),
            "w_down_e": rand(lm, e, fe, d),
            "w_gate_s": rand(lm, d, fs), "w_up_s": rand(lm, d, fs), "w_down_s": rand(lm, fs, d)})
    params: Params = {"embed": rand(cfg.vocab_size, d), "out_norm": jnp.ones((d,), dt),
                      "blocks": blocks}
    if not cfg.tie_embeddings:
        params["lm_head"] = rand(d, cfg.vocab_size)
    return params
