"""Window-attention layers beside full-attention layers, a gated attention
output, a leading dense layer then sigmoid-routed experts (``laguna``), next
to ``models/llama.py``, ``models/mla_moe.py`` and ``models/ssm_hybrid.py``.

``models.llama.forward`` / ``forward_decode_paged`` / ``make_cache`` /
``init_params`` hand a config whose ``family`` is ``swa_moe`` to the twins
here, so the batcher, the block pool, the table and the sampling are the ones
every other family uses. What differs:

* **Stacks by what a leaf belongs to.** Attention leaves are stacked by the
  layer's KIND, ``blocks.full`` [n_kv_layers, ...] and ``blocks.win``
  [n_win_layers, ...]: the two kinds have other head counts (``n_heads`` /
  ``win_n_heads`` over the same kv heads), so their ``wq`` / ``wg`` / ``wo``
  have other shapes. FFN leaves are stacked by the FFN's form,
  ``blocks.dense`` (the leading ``n_dense_layers``) and ``blocks.moe`` (the
  expert layers: ``models/experts.py``, the layer ``mla_moe`` runs). The
  leading layers run one by one; the others as ONE ``lax.scan`` over the
  periods of ``cfg.layer_types`` (window x3, full) and inside it one scan
  over each run of a kind, each layer taking its weights out of the whole
  stacks at its own place (``ssm_hybrid._layers`` says why).
* **Rotary tables by kind.** A full layer rotates the first ``rope_dim`` dims
  of a head with YaRN frequencies, cos and sin times ``rope_attn_factor``; a
  window layer ``win_rope_dim`` dims with plain frequencies over
  ``win_rope_theta``.
* **The gate.** g = sigmoid(h W_g), one number a head from the layer's normed
  input, times the head's attention output before ``wo``.
* **Only the full layers hold paged KV**: the pool's layer axis is
  ``cfg.n_kv_layers``. A window layer sees the last ``window`` keys, its own
  among them, and keeps exactly those: a RING a slot,
  ``[n_win_layers, rows, Hkv, window, D]``, the key of position p at place
  p mod window, indexed by slot and not by table. ``ops.kvcache.WithState``
  carries K's ring beside the K cache and V's beside the V cache, so admits
  write it, chunked admits carry it from chunk to chunk, suspend and resume
  take it. Prefill READS the ring before it writes it: a chunk's queries see
  the ring's keys (put in position order) and the chunk's own, in blocks of
  ``_Q_BLOCK`` queries over the ``window + _Q_BLOCK`` keys a block can see
  (plain XLA: 6 GFLOP a layer and chunk at the published widths, against 100
  of the full layers' at 16 k), and the ring that comes back holds the last
  ``window`` REAL positions (``logit_positions + 1`` positions of a row are
  real; a row with none keeps its ring). Decode writes the new key at its
  place and attends over the slot's ring in one Pallas call a layer
  (``ops.paged_attention.window_decode_attention``): no table, no walk, the
  same bytes whatever the context.

Norms, the router, the gate's sigmoid and softmax run in float32; products
take the weights' dtype as in the other families.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from ..ops.flash_attention import (
    chunk_block_multiple,
    flash_attention_auto,
    flash_attention_chunk_auto,
)
from ..ops.kvcache import WithState, kv_pool_write_rows, kv_update_slice
from ..ops.layers import (
    apply_rope,
    gqa_attention,
    gqa_attention_hmajor,
    rms_norm,
    swiglu,
    yarn_frequencies,
)
from ..ops.wquant import flat_rows, mm
from .config import ModelConfig
from .experts import expert_path, moe_ffn, split_stacks

Params = dict[str, Any]

# queries of a window layer attended together in prefill: [B, H, block,
# window + block] f32 scores (the serving chunk is one block)
_Q_BLOCK = 256
# where the ring has its row axis ([layers, rows, ...]: layer-major, as the
# layer scan reads and writes it)
RING_AXES = (1,)
_ATTN = {"full": "full", "window": "win"}  # a layer kind's attention stack


def ring_shape(cfg: ModelConfig, rows: int) -> tuple[int, ...]:
    return (cfg.n_win_layers, rows, cfg.n_kv_heads, cfg.window, cfg.head_dim)


def ring_bytes_per_slot(cfg: ModelConfig) -> int:
    """Device bytes of one slot's rings, K and V (what admission prices a
    slot at beside its KV blocks)."""
    return 2 * math.prod(ring_shape(cfg, 1)) * jnp.dtype(cfg.dtype).itemsize


def make_state(cfg: ModelConfig, rows: int):
    """Zeroed rings for ``rows`` rows: (K's ``st``, its axes), (V's, its)."""
    dt = jnp.dtype(cfg.dtype)
    return tuple(((jnp.zeros(ring_shape(cfg, rows), dt),), RING_AXES) for _ in range(2))


def make_cache(cfg: ModelConfig, batch: int, seq_len: int | None = None,
               dtype: str | None = None):
    """Zeroed row caches of the full layers [B, n_kv_layers, Hkv, S, D], each
    with its rows' zeroed rings beside it."""
    if cfg.kv_quant == "int8":
        raise NotImplementedError(
            "TPU_KV_QUANT=int8 is not implemented for window-attention models: "
            "the ring has no scale leaf and its kernel reads plain rows")
    s = seq_len or cfg.max_seq_len
    shape = (batch, cfg.n_kv_layers, cfg.n_kv_heads, s, cfg.head_dim)
    dt = jnp.dtype(dtype or cfg.dtype)
    return tuple(WithState(jnp.zeros(shape, dt), st, ax) for st, ax in make_state(cfg, batch))


# ---------------------------------------------------------------------------
# the layer plan
# ---------------------------------------------------------------------------


def layer_plan(cfg: ModelConfig):
    """(leading, periods, runs, tail) of the layers in model order. A layer is
    (kind, place in its kind's attention stack, place in its FFN stack);
    ``leading`` lists the ``n_dense_layers`` dense-FFN layers, ``tail`` the
    layers of a last, partial period. In between lie ``periods`` repeats of
    the shortest pattern of kinds: ``runs`` = [(kind, first of its kind in the
    period, layers, first layer of the run in the period)], and
    ``base`` / ``per`` give where period i's layers lie in the stacks."""
    kinds = cfg.layer_types
    if len(kinds) != cfg.n_layers or set(kinds) - {"full", "window"}:
        raise ValueError(f"layer_types {kinds} does not name {cfg.n_layers} full/window layers")
    nd = cfg.n_dense_layers
    at = {"full": 0, "window": 0}
    flat = []
    for i, kind in enumerate(kinds):
        flat.append((kind, at[kind], i if i < nd else i - nd))
        at[kind] += 1
    rest = kinds[nd:]
    p = next((p for p in range(1, len(rest) + 1)
              if all(rest[i] == rest[i % p] for i in range(len(rest)))), 1)
    periods = len(rest) // p
    runs, seen = [], {"full": 0, "window": 0}
    for j, kind in enumerate(rest[:p] if periods else ()):
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, seen[kind], 1, j])
        seen[kind] += 1
    base = {k: sum(kk == k for kk in kinds[:nd]) for k in at}
    per = {k: seen[k] for k in at}
    return {"leading": flat[:nd], "periods": periods, "period": p,
            "runs": [tuple(r) for r in runs], "base": base, "per": per,
            "tail": flat[nd + periods * p:]}


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def rope_tables(cfg: ModelConfig, positions: jax.Array):
    """{kind: (cos, sin, rotary dims)} at ``positions`` [B, T]."""
    def table(inv_freq, factor):
        ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq)
        return jnp.cos(ang) * factor, jnp.sin(ang) * factor

    fd = cfg.rope_dim or cfg.head_dim
    wd = cfg.win_rope_dim or cfg.head_dim
    full = yarn_frequencies(fd, cfg.rope_theta, cfg.rope_factor, cfg.rope_orig_ctx,
                            cfg.rope_beta_fast, cfg.rope_beta_slow)
    return {"full": table(full, cfg.rope_attn_factor) + (fd,),
            "window": table(yarn_frequencies(wd, cfg.win_rope_theta), 1.0) + (wd,)}


def _rotate(x: jax.Array, table) -> jax.Array:
    """The first ``dims`` dims of every head rotated, the others as they are."""
    cos, sin, dims = table
    if dims == x.shape[-1]:
        return apply_rope(x, cos, sin)
    return jnp.concatenate([apply_rope(x[..., :dims], cos, sin), x[..., dims:]], axis=-1)


def _qkvg(h, p: Params, cfg: ModelConfig, kind: str, tables):
    """q [B,T,H,D] and k [B,T,Hkv,D] rotated, v, and the gate [B,T,H] f32
    (None without ``attn_gate``); H is the kind's head count."""
    b, t, _ = h.shape
    heads = cfg.win_n_heads if kind == "window" else cfg.n_heads
    q, k, v = flat_rows(mm(h, p["wq"]), mm(h, p["wk"]), mm(h, p["wv"]))
    q = q.reshape(b, t, heads, cfg.head_dim)
    k = k.reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
    gate = jax.nn.sigmoid(mm(h, p["wg"]).astype(jnp.float32)) if cfg.attn_gate else None
    return _rotate(q, tables[kind]), _rotate(k, tables[kind]), v, gate


def _attn_out(o, gate, p: Params):
    """[B,T,H,D] -> the layer's attention output: a head times its gate, wo."""
    if gate is not None:
        o = o * gate[..., None].astype(o.dtype)
    return mm(o.reshape(o.shape[0], o.shape[1], -1), p["wo"])


def ring_in_order(ring: jax.Array, start: jax.Array) -> jax.Array:
    """[B, Hkv, R, D] ring -> the same keys by position: entry j is position
    start - R + j (place (start + j) mod R; junk where that is negative)."""
    r = ring.shape[2]
    idx = jnp.mod(start[:, None] + jnp.arange(r, dtype=jnp.int32)[None, :], r)
    return jnp.take_along_axis(ring, idx[:, None, :, None], axis=2)


def ring_after(keys: jax.Array, start: jax.Array, valid: jax.Array, r: int) -> jax.Array:
    """The ring after ``valid`` [B] more real positions: ``keys`` [B, Hkv,
    R + T, D] holds positions start - R .. start + T - 1 (``ring_in_order``
    then the chunk's own); place i gets the latest position below start +
    valid that belongs there. A row with valid == 0 gets its ring back."""
    last = (start + valid - 1)[:, None]
    place = jnp.arange(r, dtype=jnp.int32)[None, :]
    pos = last - jnp.mod(last - place, r)
    return jnp.take_along_axis(keys, (pos - start[:, None] + r)[:, None, :, None], axis=2)


def window_attention(q, keys, values, start: jax.Array, window: int, scale: float) -> jax.Array:
    """Attention of T queries at positions start .. start + T - 1 over the
    last ``window`` keys each, its own among them. ``keys`` / ``values`` [B,
    Hkv, R + T, D] hold positions start - R .. start + T - 1 (R >= window - 1).
    A block of ``_Q_BLOCK`` queries reads only the R + block keys it can see.
    Returns [B, T, Hq, D] in q.dtype."""
    b, t, hq, d = q.shape
    hkv, r = keys.shape[1], keys.shape[2] - t
    blk = _Q_BLOCK if t % _Q_BLOCK == 0 else t
    span = r + blk

    def block(i):
        first = i * blk
        qb = jax.lax.dynamic_slice_in_dim(q, first, blk, axis=1)
        kb = jax.lax.dynamic_slice_in_dim(keys, first, span, axis=2)
        vb = jax.lax.dynamic_slice_in_dim(values, first, span, axis=2)
        q_pos = start[:, None] + first + jnp.arange(blk, dtype=jnp.int32)[None, :]
        k_pos = start[:, None] - r + first + jnp.arange(span, dtype=jnp.int32)[None, :]
        behind = q_pos[:, :, None] - k_pos[:, None, :]
        mask = (k_pos[:, None, :] >= 0) & (behind >= 0) & (behind < window)
        return gqa_attention_hmajor(qb, kb.astype(q.dtype), vb.astype(q.dtype), mask, scale)

    if blk == t:
        return block(jnp.zeros((), jnp.int32))
    out = jax.lax.map(block, jnp.arange(t // blk, dtype=jnp.int32))  # [T/blk, B, blk, H, D]
    return jnp.moveaxis(out, 0, 1).reshape(b, t, hq, d)


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------


def _layers(params: Params, cfg: ModelConfig, x, carry, attend, live=None, mesh=None):
    """All layers in model order. ``attend[kind](h, p, carry, place) -> (out,
    carry)`` is the caller's (row caches or pools); ``place`` is the layer's
    place in its kind's attention stack. Returns (x, carry, the expert
    layers' counters [n_moe_layers, 3] or None without ``live``).

    Where the expert layers take the hit list or the grouped form
    (``experts.expert_path``) the three expert stacks are closed over WHOLE
    and a layer passes its place in them (``mla_moe._layers`` says why)."""
    plan = layer_plan(cfg)
    blocks = params["blocks"]
    rows = x.shape[0] * x.shape[1]
    form, whole, moe = "dense", None, blocks.get("moe")
    if moe is not None:
        form = expert_path(cfg, rows, moe, mesh)
        if form != "dense":
            whole, moe = split_stacks(moe)
    stats = None if live is None else jnp.zeros((cfg.n_moe_layers, 3), jnp.int32)

    def take(stack, place):
        # ONE slice a weight, out of the whole stack at the layer's own place
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, place, axis=0, keepdims=False), stack)

    def one(c, kind, a_place, f_place, dense: bool):
        x, carry, stats = c
        pa = take(blocks[_ATTN[kind]], a_place)
        with jax.named_scope("seq/attn" if kind == "full" else "seq/window"):
            out, carry = attend[kind](rms_norm(x, pa["attn_norm"], cfg.rms_eps), pa, carry, a_place)
            x = x + out
        pf = take(blocks["dense"] if dense else moe, f_place)
        with jax.named_scope("ffn"):
            h = rms_norm(x, pf["ffn_norm"], cfg.rms_eps)
            if dense:
                with jax.named_scope("mlp"):
                    y = swiglu(h, pf["w_gate"], pf["w_up"], pf["w_down"], cfg.mlp_act)
            else:
                y, st = moe_ffn(h, pf, cfg, live, form, whole, f_place)
                if stats is not None:
                    with jax.named_scope("router"):  # the layer's counters, beside the others
                        stats = jax.lax.dynamic_update_slice(
                            stats, st[None], (f_place, jnp.zeros((), jnp.int32)))
            return x + y, carry, stats

    c = (x, carry, stats)
    for kind, a_place, f_place in plan["leading"]:
        c = one(c, kind, a_place, f_place, True)

    def period(c, i):
        for kind, first, count, at in plan["runs"]:
            a0 = plan["base"][kind] + i * plan["per"][kind] + first
            f0 = i * plan["period"] + at
            if count == 1:
                c = one(c, kind, a0, f0, False)
            else:
                def step(c, j, kind=kind, a0=a0, f0=f0):
                    return one(c, kind, a0 + j, f0 + j, False), None

                c, _ = jax.lax.scan(step, c, jnp.arange(count, dtype=jnp.int32))
        return c, None

    if plan["periods"]:
        c, _ = jax.lax.scan(period, c, jnp.arange(plan["periods"], dtype=jnp.int32))
    for kind, a_place, f_place in plan["tail"]:
        c = one(c, kind, a_place, f_place, False)
    return c


def _embed(params: Params, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    with jax.named_scope("embed"):
        return params["embed"][tokens].astype(jnp.dtype(cfg.dtype)) * cfg.embedding_scale


def forward(
    params: Params, cfg: ModelConfig, tokens: jax.Array,
    k_cache: WithState, v_cache: WithState,
    start_pos: jax.Array, attn_window: int | None = None, mesh=None,
    ring_slot=None, logit_positions=None, fresh_prefill: bool = False,
    uniform_start: bool = False,
):
    """``models.llama.forward``'s contract over row caches with rings: T
    positions of B rows that go on from the rows' rings (zeros at a start; a
    chunk after the first finds what the chunk before left). The rings that
    come back hold the last ``window`` REAL positions of each row:
    ``logit_positions + 1`` positions of a row are real (all T without it;
    none where it is negative: a row whose prompt ended in an earlier chunk
    of a group)."""
    if ring_slot is not None:
        raise NotImplementedError(
            "window-attention models are served on the paged pool (KV_PAGED=1): "
            "the shared-ring cache layout holds every layer's whole context")
    b, t = tokens.shape
    s_max = k_cache.shape[3]
    win = attn_window if (attn_window is not None and attn_window < s_max) else s_max
    positions = start_pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    valid = (jnp.full((b,), t, jnp.int32) if logit_positions is None
             else jnp.clip(logit_positions.astype(jnp.int32) + 1, 0, t))
    with jax.named_scope("seq"):  # both kinds' rotary tables
        tables = rope_tables(cfg, positions)
    zero = jnp.zeros((), jnp.int32)
    flash = cfg.use_flash_attention and t > 1
    # the chunk kernel tiles the cache window: its extent must divide
    on_cache = flash and uniform_start and not fresh_prefill and (
        win % chunk_block_multiple(False, jnp.dtype(cfg.dtype).itemsize) == 0)
    with jax.named_scope("seq/attn"):
        key_pos = jnp.arange(t if fresh_prefill else win, dtype=jnp.int32)
        mask = key_pos[None, None, :] <= positions[:, :, None]
    (rk,), (rv,) = k_cache.st, v_cache.st

    def full(h, p, carry, place):
        kc, vc, rk, rv = carry
        q, k, v, gate = _qkvg(h, p, cfg, "full", tables)

        def write(cache_b, rows_b, s):  # [L, Hkv, S, D] <- [Hkv, T, D] at (place, 0, s, 0)
            return kv_update_slice(cache_b, rows_b[None], (place, zero, s, zero))

        kc = jax.vmap(write)(kc, k.transpose(0, 2, 1, 3), start_pos)
        vc = jax.vmap(write)(vc, v.transpose(0, 2, 1, 3), start_pos)

        def window(cache):  # the layer's [B, Hkv, win, D]
            return jax.lax.dynamic_slice(
                cache, (zero, place, zero, zero, zero),
                (b, 1, cfg.n_kv_heads, win, cfg.head_dim))[:, 0].astype(q.dtype)

        if fresh_prefill:  # start_pos == 0: the fresh keys are all there is
            o = (flash_attention_auto(q, k, v, cfg.attn_scale) if flash
                 else gqa_attention(q, k, v, mask, cfg.attn_scale))
        elif on_cache:
            o = flash_attention_chunk_auto(q, window(kc), window(vc), cfg.attn_scale, start_pos[0])
        else:
            o = gqa_attention_hmajor(q, window(kc), window(vc), mask, cfg.attn_scale)
        return _attn_out(o, gate, p), (kc, vc, rk, rv)

    def extend(ring, new, place):
        """(the ring with the chunk's last real positions in it, the keys the
        chunk's queries see: positions start - R .. start + T - 1)."""
        old = jax.lax.dynamic_index_in_dim(ring, place, axis=0, keepdims=False)
        seq = jnp.concatenate(
            [ring_in_order(old, start_pos), new.transpose(0, 2, 1, 3).astype(old.dtype)], axis=2)
        after = ring_after(seq, start_pos, valid, cfg.window)
        return jax.lax.dynamic_update_slice(ring, after[None], (place, zero, zero, zero, zero)), seq

    def windowed(h, p, carry, place):
        kc, vc, rk, rv = carry
        q, k, v, gate = _qkvg(h, p, cfg, "window", tables)
        (rk, keys), (rv, values) = extend(rk, k, place), extend(rv, v, place)
        o = window_attention(q, keys, values, start_pos, cfg.window, cfg.attn_scale)
        return _attn_out(o, gate, p), (kc, vc, rk, rv)

    x, (kc, vc, rk, rv), _ = _layers(
        params, cfg, _embed(params, cfg, tokens), (k_cache.kv, v_cache.kv, rk, rv),
        {"full": full, "window": windowed}, mesh=mesh)
    from .llama import lm_head_logits

    at = None if logit_positions is None else jnp.maximum(logit_positions, 0)
    logits = lm_head_logits(params, cfg, x, at, t)
    return logits, WithState(kc, (rk,), RING_AXES), WithState(vc, (rv,), RING_AXES)


def forward_decode_paged(
    params: Params, cfg: ModelConfig, tokens: jax.Array,
    k_pool: WithState, v_pool: WithState,  # pools [NB, Lkv, Hkv, T, D] + the slots' rings
    tbl: jax.Array, start_pos: jax.Array, mesh=None,
):
    """``models.llama.forward_decode_paged``'s contract, one position a slot:
    a full layer writes its row into the pool and attends over the slot's
    table (the paged decode kernel), a window layer writes it into the slot's
    ring at place pos mod window and attends over the ring (the ring kernel).
    Row i of the batch IS slot i of the rings. Returns (logits, k_pool,
    v_pool, the expert layers' counters as ``mla_moe`` returns them)."""
    from ..ops.paged_attention import paged_decode_attention_auto, window_decode_attention_auto

    b, w = tokens.shape
    if w != 1:
        raise NotImplementedError(
            "window-attention models decode one position a step: a speculative "
            "bundle would overwrite ring places that rejected drafts leave "
            "wrong, and the ring keeps nothing to go back to (SPEC_DECODE=0)")
    with jax.named_scope("seq"):  # both kinds' rotary tables
        tables = rope_tables(cfg, start_pos[:, None])
    live = (tbl[:, 0] > 0).astype(jnp.float32)
    with jax.named_scope("seq/window"):  # a row's place in its rings
        rows = jnp.arange(b, dtype=jnp.int32)[:, None]
        heads = jnp.arange(cfg.n_kv_heads, dtype=jnp.int32)[None, :]
        at = jnp.mod(start_pos, cfg.window)[:, None]
    (rk,), (rv,) = k_pool.st, v_pool.st

    def full(h, p, carry, place):
        kp, vp, rk, rv = carry
        q, k, v, gate = _qkvg(h, p, cfg, "full", tables)
        kp = kv_pool_write_rows(kp, k, tbl, start_pos, place)
        vp = kv_pool_write_rows(vp, v, tbl, start_pos, place)
        o = paged_decode_attention_auto(q, kp, vp, tbl, start_pos, place, cfg.attn_scale)
        return _attn_out(o, gate, p), (kp, vp, rk, rv)

    def windowed(h, p, carry, place):
        kp, vp, rk, rv = carry
        q, k, v, gate = _qkvg(h, p, cfg, "window", tables)
        # every index but the minor axis explicit: each update is one row of
        # the ring as it lies (kv_pool_write_rows says why)
        rk = rk.at[place, rows, heads, at].set(k[:, 0].astype(rk.dtype))
        rv = rv.at[place, rows, heads, at].set(v[:, 0].astype(rv.dtype))
        o = window_decode_attention_auto(q, rk, rv, start_pos, place, cfg.attn_scale)
        return _attn_out(o, gate, p), (kp, vp, rk, rv)

    x, (kp, vp, rk, rv), stats = _layers(
        params, cfg, _embed(params, cfg, tokens), (k_pool.kv, v_pool.kv, rk, rv),
        {"full": full, "window": windowed}, live, mesh)
    from .llama import lm_head_logits

    return (lm_head_logits(params, cfg, x, None, w), WithState(kp, (rk,), RING_AXES),
            WithState(vp, (rv,), RING_AXES), stats)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Random small-scale init; the tree is what a loader of the family would
    build (``benchmark/references/swa_gated_moe.py param_shapes`` names it)."""
    dt = jnp.dtype(cfg.dtype)
    keys = iter(jax.random.split(key, 48))

    def rand(*shape):
        return (jax.random.normal(next(keys), shape, jnp.float32) * 0.02).astype(dt)

    d, hd, hkv = cfg.d_model, cfg.head_dim, cfg.n_kv_heads

    def attn(L: int, heads: int) -> Params:
        out = {"attn_norm": jnp.ones((L, d), dt), "wq": rand(L, d, heads * hd),
               "wk": rand(L, d, hkv * hd), "wv": rand(L, d, hkv * hd),
               "wo": rand(L, heads * hd, d)}
        return out | ({"wg": rand(L, d, heads)} if cfg.attn_gate else {})

    blocks: Params = {}
    if cfg.n_kv_layers:
        blocks["full"] = attn(cfg.n_kv_layers, cfg.n_heads)
    if cfg.n_win_layers:
        blocks["win"] = attn(cfg.n_win_layers, cfg.win_n_heads)
    ld, lm = cfg.n_dense_layers, cfg.n_moe_layers
    if ld:
        ff = cfg.d_ff
        blocks["dense"] = {"ffn_norm": jnp.ones((ld, d), dt), "w_gate": rand(ld, d, ff),
                           "w_up": rand(ld, d, ff), "w_down": rand(ld, ff, d)}
    if lm:
        e, fe, fs = cfg.n_experts, cfg.moe_d_ff, cfg.n_shared_experts * cfg.moe_d_ff
        blocks["moe"] = {
            "ffn_norm": jnp.ones((lm, d), dt), "router": rand(lm, d, e), "e_bias": rand(lm, e),
            "w_gate_e": rand(lm, e, d, fe), "w_up_e": rand(lm, e, d, fe),
            "w_down_e": rand(lm, e, fe, d),
            "w_gate_s": rand(lm, d, fs), "w_up_s": rand(lm, d, fs), "w_down_s": rand(lm, fs, d)}
    params: Params = {"embed": rand(cfg.vocab_size, d), "out_norm": jnp.ones((d,), dt),
                      "blocks": blocks}
    if not cfg.tie_embeddings:
        params["lm_head"] = rand(d, cfg.vocab_size)
    return params
