"""Lightning linear-attention layers beside block-sparse attention layers, a
dense SwiGLU in every layer (``minicpm_sala``), next to ``models/llama.py``,
``models/mla_moe.py``, ``models/ssm_hybrid.py``, ``models/swa_moe.py`` and
``models/gdn_moe.py``.

``models.llama.forward`` / ``forward_decode_paged`` / ``make_cache`` /
``init_params`` hand a config whose ``family`` is ``sala`` to the twins here.
The file is ``gdn_moe``'s shape with another recurrence and another attention,
and takes ``ssm_hybrid``'s layer scan (``_layers``, with its own dense FFN
half: the SwiGLU's leaves lie in each mixer's stack) as it is. ``layer_types``
is a LIST here, not a period: the published order (sparse, 8 lightning,
sparse, 6 lightning, 2 sparse, ...) repeats nothing, so ``period_plan`` finds
one period of nine runs and ``_layers`` scans each run.

* **A lightning layer** (``blocks.linear``): q, k, v and an output gate from
  four plain projections; q and k RMS-normalised a head (gains ``q_norm`` /
  ``k_norm``) and rotated over the whole head by the token's position, q
  times d^-0.5; the state S [H, d, d] float32 a slot decays by a constant of
  (layer, head) and takes k v^T (``ops/lightning.py``: three products a chunk
  in prefill, one Pallas call over the slots that hold a request in decode);
  the read-out S^T q of all heads is RMS-normalised TOGETHER (gain
  ``out_norm`` [H d]), times sigmoid(gate), then ``wo``. The rates are the
  leaf ``decay`` [layers, H], held as logits (a = sigmoid(leaf), lambda =
  exp(-a)): the initialiser writes Lightning Attention's table at the
  layers' PUBLISHED indices (``cfg.stage_first_layer`` / ``stage_depth``).
  The state rides beside V's cache as Qwen3-Next's does (``ops.kvcache.
  WithState``; ``ssm_hybrid``'s docstring has the rules for padding, for a
  slot without a request and for a replayed position); there is no
  convolution, so K's side holds no tail.
* **A sparse layer** (``blocks.attn``): ``wq`` makes the queries and an
  elementwise gate ([q | gate]); q and k RMS-normalised a head; NO rotary
  embedding. A query that sees n <= ``sparse_dense_len`` keys attends to all
  of them; past that, to the keys of ``sparse_topk`` blocks of
  ``sparse_block`` a kv head: the first ``sparse_init_blocks``, every block
  that meets the last ``sparse_window`` keys, and the best of the others by
  R_b = max over the pooled keys that meet block b of sum over the group's
  query heads of softmax_j(q . c_j d^-0.5), c_j the mean of the
  ``sparse_kernel`` keys from 16 j on (``block_scores``, ``keep_blocks``).
  The pooled keys are a third thing the layer keeps, a slot, beside K's cache
  ([Ls, rows, Hkv, max_seq / stride, D], with ``seen``): prefill writes the
  ones a chunk completes from the row's key cache, decode the one a step
  completes from the pool; one that is not complete yet is never read
  (whatever an earlier request left there). **Decode** picks the blocks
  (sorted, so the frontier block is last and partly valid) and walks them
  in the pool (``ops.paged_attention.paged_decode_attention_picked``: a table
  a kv head); a row still under ``dense_len`` walks all its blocks through
  the same call. **Prefill** scores every query of a chunk against the
  pooled keys of its row so far and applies its picks as a mask a (query, key
  block) over plain attention in blocks of keys (``masked_attention``: the
  flash chunk kernel's XLA form; a chunk that ends under ``dense_len`` takes
  the kernel itself). What a query computes never depends on a later token.
* **muP scalings** are ``cfg.embedding_scale``, ``residual_scale`` and
  ``logit_scale`` (Granite's fields).

The state, the decays, the norms, the selection's scores and softmax run in
float32; products take the weights' dtype as in the other families.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..ops import lightning, ssm_scan
from ..ops.flash_attention import (
    chunk_block_multiple,
    flash_attention_auto,
    flash_attention_chunk_auto,
)
from ..ops.kvcache import WithState, kv_pool_write_rows, kv_update_slice, table_rows_in_use
from ..ops.layers import apply_rope, gqa_attention, gqa_attention_hmajor, rms_norm, rope_cos_sin
from ..ops.wquant import flat_rows, mm
from .config import ModelConfig
from .gdn_moe import _attn_out
from .ssm_hybrid import V_AXES, _embed, _layers, state_bytes, zeroed_state

Params = dict[str, Any]

# the rows' axis of K's state leaves (the pooled keys, layer-major; ``seen``)
K_AXES = (1, 0)

_NEG = -1e30
# keys one turn of ``masked_attention``'s loop takes, in sparse blocks
_MASKED_KEY_BLOCKS = 16


def check(cfg: ModelConfig) -> None:
    """The sizes the two paths of a sparse layer lean on."""
    k, s, blk = cfg.sparse_kernel, cfg.sparse_stride, cfg.sparse_block
    forced = cfg.sparse_init_blocks + cfg.sparse_window // blk + 1
    if (k % s or blk % s or cfg.sparse_window % blk or cfg.max_seq_len % blk
            or forced > cfg.sparse_topk or cfg.sparse_dense_len < cfg.sparse_topk * blk
            or cfg.lin_k_heads != cfg.lin_v_heads):
        raise ValueError(
            f"{cfg.arch}: sparse sizes kernel {k}, stride {s}, block {blk}, window "
            f"{cfg.sparse_window}, topk {cfg.sparse_topk}, dense_len {cfg.sparse_dense_len} "
            f"at max_seq_len {cfg.max_seq_len}: the kernel and the block must be whole "
            "strides, the window and the context whole blocks, the forced blocks (initial, "
            "window, frontier) at most topk, and dense_len at least topk blocks")


def state_shapes(cfg: ModelConfig, rows: int) -> tuple[tuple, tuple]:
    """((pooled keys' shape, seen shape), (state shape,)) for ``rows`` rows:
    the sparse layers' pooled keys layer-major (a layer's step writes its
    own), the lightning layers' state a row."""
    return (((cfg.n_kv_layers, rows, cfg.n_kv_heads, cfg.sparse_pooled_len, cfg.head_dim),
             (rows,)),
            ((rows, cfg.n_lin_layers, cfg.lin_v_heads, cfg.lin_k_dim, cfg.lin_v_dim),))


def make_state(cfg: ModelConfig, rows: int):
    """Zeroed state for ``rows`` rows: (K's ``st``, its axes), (V's, its)."""
    return zeroed_state(cfg, state_shapes(cfg, rows), K_AXES)


def state_bytes_per_slot(cfg: ModelConfig) -> int:
    """Device bytes one slot's state and pooled keys take (what admission
    prices a slot at beside its KV blocks)."""
    return state_bytes(cfg, state_shapes(cfg, 1))


def make_cache(cfg: ModelConfig, batch: int, seq_len: int | None = None,
               dtype: str | None = None):
    """Zeroed row caches [B, n_kv_layers, Hkv, S, D], each with its rows'
    zeroed state beside it."""
    check(cfg)
    if cfg.kv_quant == "int8":
        raise NotImplementedError(
            "TPU_KV_QUANT=int8 is not implemented for linear-attention models: "
            "the family's caches ride with a float32 state that has no scale leaf")
    s = seq_len or cfg.max_seq_len
    dt = jnp.dtype(dtype or cfg.dtype)
    shape = (batch, cfg.n_kv_layers, cfg.n_kv_heads, s, cfg.head_dim)
    return tuple(WithState(jnp.zeros(shape, dt), st, ax) for st, ax in make_state(cfg, batch))


# ---------------------------------------------------------------------------
# the lightning layer
# ---------------------------------------------------------------------------


def _lightning_in(h, p: Params, cfg: ModelConfig, positions):
    """(q, k [.., H, d] f32 normalised, rotated, q scaled; v [.., H, d]; the
    gate's input [.., H d]) of positions ``positions`` [B, T]."""
    b, t, _ = h.shape
    hd = (b, t, cfg.lin_v_heads, cfg.lin_k_dim)
    q, k, v = flat_rows(mm(h, p["wq"]), mm(h, p["wk"]), mm(h, p["wv"]))
    q, k, v = q.reshape(hd), k.reshape(hd), v.reshape(b, t, cfg.lin_v_heads, cfg.lin_v_dim)
    cos, sin = rope_cos_sin(positions, cfg.lin_k_dim, cfg.rope_theta)
    f32 = jnp.float32
    q = apply_rope(rms_norm(q.astype(f32), p["q_norm"].astype(f32), cfg.rms_eps), cos, sin)
    k = apply_rope(rms_norm(k.astype(f32), p["k_norm"].astype(f32), cfg.rms_eps), cos, sin)
    return q * cfg.lin_k_dim**-0.5, k, v, mm(h, p["wg"])


def _lightning_out(o, gate, p: Params, cfg: ModelConfig):
    """o [.., H d_v] f32 -> the mixer's output: ONE norm over all the heads,
    times its gain and sigmoid(gate), then the output projection."""
    y = rms_norm(o, p["out_norm"].astype(jnp.float32), cfg.rms_eps)
    y = y * jax.nn.sigmoid(gate.astype(jnp.float32))
    return mm(y.astype(gate.dtype), p["wo"])


def lightning_prefill(h, p: Params, cfg: ModelConfig, states, layer, positions, valid):
    """The mixer over T positions of B rows: ``states`` [B, Ll, H, d, d] is
    the rows' state of all layers, this one's slice read and written at
    ``layer``. ``valid`` [B]: real positions of each row."""
    zero = jnp.zeros((), jnp.int32)
    q, k, v, gate = _lightning_in(h, p, cfg, positions)
    s0 = jax.lax.dynamic_slice_in_dim(states, layer, 1, axis=1)[:, 0]
    dt = v.dtype
    o, s1 = lightning.lightning_chunked(
        q.astype(dt), k.astype(dt), v, lightning.rates(p["decay"]), valid, s0)
    states = jax.lax.dynamic_update_slice(states, s1[:, None], (zero, layer, zero, zero, zero))
    return _lightning_out(o.reshape(o.shape[:2] + (-1,)), gate, p, cfg), states


def lightning_step(h, p: Params, cfg: ModelConfig, states, layer, live, fresh, positions):
    """The mixer over ONE position of the ``live`` slots (``ssm_scan.
    LiveSlots``), their state updated in place in the pool. ``fresh`` [B]
    bool: live rows that consume their position (the other live rows read
    their state as it is; a row that is not live gives zeros)."""
    q, k, v, gate = _lightning_in(h, p, cfg, positions)
    lam = jnp.exp(-lightning.rates(p["decay"]))
    decay = jnp.where(fresh[:, None], lam[None, :], 1.0)
    v = jnp.where(fresh[:, None, None], v[:, 0].astype(jnp.float32), 0.0)
    states, o = lightning.lightning_step_auto(states, layer, live, decay, q[:, 0], k[:, 0], v)
    return _lightning_out(o, gate[:, 0], p, cfg)[:, None], states


# ---------------------------------------------------------------------------
# the sparse layer: pooled keys, block scores, picks
# ---------------------------------------------------------------------------


def _qkvg(h, p: Params, cfg: ModelConfig):
    """q [B,T,H,D] and k [B,T,Hkv,D] normalised a head (no rotary), v, and
    the gate [B,T,H x D] f32."""
    b, t, _ = h.shape
    hd = cfg.n_heads * cfg.head_dim
    qg, k, v = flat_rows(mm(h, p["wq"]), mm(h, p["wk"]), mm(h, p["wv"]))
    q = qg[..., :hd].reshape(b, t, cfg.n_heads, cfg.head_dim)
    gate = jax.nn.sigmoid(qg[..., hd:].astype(jnp.float32))
    k = k.reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
    return rms_norm(q, p["q_norm"], cfg.rms_eps), rms_norm(k, p["k_norm"], cfg.rms_eps), v, gate


def pool_keys(keys: jax.Array, cfg: ModelConfig, count: int) -> jax.Array:
    """``count`` pooled keys [.., Hkv, count, D] f32 from the keys [.., Hkv,
    (count - 1) stride + kernel, D] they cover: each the mean of ``kernel``
    keys, one every ``stride``."""
    s, parts = cfg.sparse_stride, cfg.sparse_kernel // cfg.sparse_stride
    lead = keys.shape[:-2]
    sums = keys.astype(jnp.float32).reshape(lead + (count + parts - 1, s, keys.shape[-1])).sum(-2)
    return sum(sums[..., e:e + count, :] for e in range(parts)) / cfg.sparse_kernel


def pooled_exist(n, count: int, cfg: ModelConfig):
    """[.., count] bool: the pooled keys a query that sees ``n`` [..] keys may
    read: one exists once ALL its ``kernel`` keys do."""
    j = jnp.arange(count, dtype=jnp.int32)
    return j * cfg.sparse_stride + cfg.sparse_kernel <= n[..., None]


def block_scores(q, pooled, n, cfg: ModelConfig):
    """R [B, Hkv, T, blocks] f32 of queries q [B, T, Hq, D] that see ``n`` [B,
    T] keys each, against the row's pooled keys [B, Hkv, NP, D]: a block's
    score is the largest, over the pooled keys that meet it, of the group's
    summed softmax scores; +inf for a block the query must take (the initial
    ones, those that meet its window), -inf for one it cannot see."""
    b, t, hq, d = q.shape
    hkv, np_ = pooled.shape[1], pooled.shape[2]
    per, extra = cfg.sparse_block // cfg.sparse_stride, cfg.sparse_kernel // cfg.sparse_stride - 1
    qg = q.reshape(b, t, hkv, hq // hkv, d).transpose(0, 2, 3, 1, 4)  # [B, Hkv, g, T, D]
    s = jnp.einsum("bhgtd,bhjd->bhgtj", qg, pooled.astype(q.dtype),
                   preferred_element_type=jnp.float32) * cfg.attn_scale
    exists = pooled_exist(n, np_, cfg)[:, None, None]
    prob = jax.nn.softmax(jnp.where(exists, s, _NEG), axis=-1)
    r = jnp.sum(jnp.where(exists, prob, 0.0), axis=2)  # [B, Hkv, T, NP]
    nb = np_ // per
    blocks = r[..., : nb * per].reshape(b, hkv, t, nb, per)
    score = jnp.max(blocks, axis=-1)
    for e in range(1, extra + 1):  # the pooled keys that begin in the block before
        before = jnp.pad(blocks[..., :-1, per - e], ((0, 0),) * 3 + ((1, 0),))
        score = jnp.maximum(score, before)
    blk = jnp.arange(nb, dtype=jnp.int32)
    n_ = n[:, None, :, None]
    forced = (blk < cfg.sparse_init_blocks) | (blk >= (n_ - cfg.sparse_window) // cfg.sparse_block)
    score = jnp.where(forced, jnp.inf, score)
    return jnp.where(blk <= (n_ - 1) // cfg.sparse_block, score, -jnp.inf)


def keep_blocks(score, n, cfg: ModelConfig):
    """[B, Hkv, T, blocks] bool: the ``sparse_topk`` blocks of largest score
    (ties to the lower index, as ``lax.top_k`` orders them), or every block
    for a query that sees at most ``sparse_dense_len`` keys."""
    kth = jax.lax.top_k(score, cfg.sparse_topk)[0][..., -1:]
    above, tie = score > kth, score == kth
    need = cfg.sparse_topk - jnp.sum(above, axis=-1, keepdims=True)
    keep = above | (tie & (jnp.cumsum(tie, axis=-1) <= need))
    return keep | (n <= cfg.sparse_dense_len)[:, None, :, None]


def masked_attention(q, k, v, keep, positions, cfg: ModelConfig):
    """Causal attention of q [B, T, Hq, D] at ``positions`` [B, T] over the
    cache window k, v [B, Hkv, W, D], a query of kv head h seeing the keys of
    the blocks ``keep`` [B, T, Hkv, W / block] allows: plain XLA in turns of
    ``_MASKED_KEY_BLOCKS`` blocks of keys with a running softmax, as many
    turns as the furthest query needs. Returns [B, T, Hq, D] in q.dtype."""
    b, t, hq, d = q.shape
    hkv, w = k.shape[1], k.shape[2]
    g, blk = hq // hkv, cfg.sparse_block
    nb = w // blk
    per = next(c for c in range(min(_MASKED_KEY_BLOCKS, nb), 0, -1) if nb % c == 0)
    kb = per * blk
    qg = q.reshape(b, t, hkv, g, d).transpose(0, 2, 3, 1, 4)  # [B, Hkv, g, T, D]
    zero = jnp.zeros((), jnp.int32)

    def turn(i, carry):
        acc, m, l = carry
        ks = jax.lax.dynamic_slice(k, (zero, zero, i * kb, zero), (b, hkv, kb, d)).astype(q.dtype)
        vs = jax.lax.dynamic_slice(v, (zero, zero, i * kb, zero), (b, hkv, kb, d)).astype(q.dtype)
        s = jnp.einsum("bhgtd,bhkd->bhgtk", qg, ks,
                       preferred_element_type=jnp.float32) * cfg.attn_scale
        at = i * kb + jnp.arange(kb, dtype=jnp.int32)
        on = jnp.repeat(jax.lax.dynamic_slice(keep, (zero, zero, zero, i * per),
                                              (b, hkv, t, per)), blk, axis=-1)
        on = on & (at[None, None, None, :] <= positions[:, None, :, None])
        s = jnp.where(on[:, :, None], s, _NEG)
        m1 = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        pr, corr = jnp.exp(s - m1), jnp.exp(m - m1)
        acc = acc * corr + jnp.einsum("bhgtk,bhkd->bhgtd", pr.astype(q.dtype), vs,
                                      preferred_element_type=jnp.float32)
        return acc, m1, l * corr + jnp.sum(pr, axis=-1, keepdims=True)

    turns = jnp.minimum(jnp.max(positions) // kb + 1, w // kb)
    acc, _, l = jax.lax.fori_loop(
        0, turns, turn,
        (jnp.zeros((b, hkv, g, t, d), jnp.float32), jnp.full((b, hkv, g, t, 1), _NEG, jnp.float32),
         jnp.zeros((b, hkv, g, t, 1), jnp.float32)))
    out = (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, t, hq, d)


def _pooled_from_rows(kc, pooled, layer, start_pos, t: int, cfg: ModelConfig):
    """The pooled keys [Ls, B, Hkv, NP, D] with those of ``layer`` that a chunk
    of ``t`` positions from ``start_pos`` [B] completes written from the rows'
    key caches ``kc`` [B, L, Hkv, S, D] (the chunk's own keys in them). One
    that covers a position past a row's last real one holds junk, and is not
    complete for any query until a later chunk or step writes it again."""
    stride, kernel = cfg.sparse_stride, cfg.sparse_kernel
    extra = kernel // stride - 1
    room = min(kc.shape[3] // stride, pooled.shape[3]) - extra
    count = min(t // stride + 1, room)
    if count <= 0:
        return pooled
    span = (count - 1) * stride + kernel
    zero = jnp.zeros((), jnp.int32)

    def one(b, pooled):  # a row's few keys in, its few pooled keys out, in place
        j0 = jnp.clip((start_pos[b] - kernel) // stride + 1, 0, room - count)
        ks = jax.lax.dynamic_slice(kc, (b, layer, zero, j0 * stride, zero),
                                   (1, 1, kc.shape[2], span, kc.shape[4]))
        new = pool_keys(ks, cfg, count).astype(pooled.dtype)  # [1, 1, Hkv, count, D]
        return jax.lax.dynamic_update_slice(pooled, new, (layer, b, zero, j0, zero))

    return jax.lax.fori_loop(0, kc.shape[0], one, pooled)


def _pooled_from_pool(kp, pooled, tbl, pos, layer, on, cfg: ModelConfig):
    """The pooled keys [Ls, B, Hkv, NP, D] with the one of ``layer`` that the
    key at ``pos`` [B] completes (where it completes one, and ``on``) written
    from the pool's keys of the slot's last ``kernel`` positions: a scatter of
    one row a (slot, kv head), in place."""
    stride, kernel, t = cfg.sparse_stride, cfg.sparse_kernel, kp.shape[3]
    n = pos + 1
    due = on & (n >= kernel) & ((n - kernel) % stride == 0)
    # a row that completes none writes past the end, which the scatter drops
    j = jnp.where(due, (n - kernel) // stride, pooled.shape[3])
    at = jnp.maximum(n[:, None] - kernel + jnp.arange(kernel, dtype=jnp.int32)[None, :], 0)
    bids = jnp.take_along_axis(tbl, jnp.clip(at // t, 0, tbl.shape[1] - 1), axis=1)  # [B, kernel]
    heads = jnp.arange(kp.shape[2], dtype=jnp.int32)
    # every index but the minor-most explicit: a row of the pool as it lies
    keys = kp[bids[:, :, None], layer, heads[None, None, :], (at % t)[:, :, None]]
    new = pool_keys(keys.transpose(0, 2, 1, 3), cfg, 1)[:, :, 0]  # [B, Hkv, D]
    rows = jnp.arange(pos.shape[0], dtype=jnp.int32)
    return pooled.at[layer, rows[:, None], heads[None, :], j[:, None]].set(
        new.astype(pooled.dtype), mode="drop")


def picked_entries(score, pos, tbl, cfg: ModelConfig, t: int):
    """What the picked walk reads for ONE query a slot, from its blocks'
    scores [B, Hkv, blocks]: (entries [B, Hkv, P] pool block ids, count [B,
    Hkv], last_len [B]). A row past ``dense_len`` walks its ``topk`` picks in
    rising order, so the frontier block, which it always picks, is last; a
    row under it walks all its blocks. A sparse block is ``block / t`` table
    entries side by side."""
    b, hkv, nb = score.shape
    per, n = cfg.sparse_block // t, pos + 1
    # a context of fewer blocks than the dense length holds never selects
    width = min(max(cfg.sparse_topk, -(-cfg.sparse_dense_len // cfg.sparse_block)), nb)
    topk = min(cfg.sparse_topk, width)
    picks = jnp.sort(jax.lax.top_k(score, topk)[1], axis=-1)
    picks = jnp.pad(picks, ((0, 0), (0, 0), (0, width - topk)))
    dense = (n <= cfg.sparse_dense_len)[:, None]
    picks = jnp.where(dense[..., None], jnp.arange(width, dtype=jnp.int32), picks)
    logical = (picks[..., None] * per + jnp.arange(per, dtype=jnp.int32)).reshape(b, hkv, -1)
    entries = jnp.take_along_axis(
        tbl[:, None, :], jnp.clip(logical, 0, tbl.shape[1] - 1), axis=2)
    count = jnp.where(dense, pos[:, None] // t + 1,
                      (topk - 1) * per + (pos[:, None] % cfg.sparse_block) // t + 1)
    return entries, jnp.broadcast_to(count, (b, hkv)), pos % t + 1


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------


def forward(
    params: Params, cfg: ModelConfig, tokens: jax.Array,
    k_cache: WithState, v_cache: WithState,
    start_pos: jax.Array, attn_window: int | None = None, mesh=None,
    ring_slot=None, logit_positions=None, fresh_prefill: bool = False,
    uniform_start: bool = False,
):
    """``models.llama.forward``'s contract over row caches with state: T
    positions of B rows that go on from the rows' state (zeros at a start;
    a chunk after the first finds what the chunk before left). The state
    that comes back is the one after each row's last REAL position:
    ``logit_positions + 1`` positions of a row are real (all T without it;
    none where it is negative: a row whose prompt ended in an earlier chunk
    of a group)."""
    if ring_slot is not None:
        raise NotImplementedError(
            "linear-attention models are served on the paged pool (KV_PAGED=1): the "
            "shared-ring cache layout rolls rows, and a state cannot be rolled")
    del mesh
    b, t = tokens.shape
    s_max = k_cache.shape[3]
    win = attn_window if (attn_window is not None and attn_window < s_max) else s_max
    positions = start_pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    valid = (jnp.full((b,), t, jnp.int32) if logit_positions is None
             else jnp.clip(logit_positions.astype(jnp.int32) + 1, 0, t))
    zero = jnp.zeros((), jnp.int32)
    flash = cfg.use_flash_attention and t > 1
    # the chunk kernel tiles the cache window: its extent must divide
    on_cache = flash and uniform_start and not fresh_prefill and (
        win % chunk_block_multiple(False, jnp.dtype(cfg.dtype).itemsize) == 0)
    # whether any query of this call can be past the dense length
    may_select = (t if fresh_prefill else win) > cfg.sparse_dense_len
    if may_select and win % cfg.sparse_block:
        raise ValueError(f"{cfg.arch}: a cache window of {win} keys is not whole sparse "
                         f"blocks of {cfg.sparse_block}")
    with jax.named_scope("seq/sparse"):
        key_pos = jnp.arange(t if fresh_prefill else win, dtype=jnp.int32)
        mask = key_pos[None, None, :] <= positions[:, :, None]
    (pooled, seen), (states,) = k_cache.st, v_cache.st

    def lightning_(h, p, carry, layer):
        kc, vc, pooled, states = carry
        out, states = lightning_prefill(h, p, cfg, states, layer, positions, valid)
        return out, (kc, vc, pooled, states)

    def sparse(h, p, carry, layer):
        kc, vc, pooled, states = carry
        q, k, v, gate = _qkvg(h, p, cfg)

        def write(cache_b, rows_b, s):  # [L, Hkv, S, D] <- [Hkv, T, D] at (layer, 0, s, 0)
            return kv_update_slice(cache_b, rows_b[None], (layer, zero, s, zero))

        kc = jax.vmap(write)(kc, k.transpose(0, 2, 1, 3), start_pos)
        vc = jax.vmap(write)(vc, v.transpose(0, 2, 1, 3), start_pos)

        def window(cache):  # the layer's [B, Hkv, win, D]
            return jax.lax.dynamic_slice(
                cache, (zero, layer, zero, zero, zero),
                (b, 1, cfg.n_kv_heads, win, cfg.head_dim))[:, 0]

        with jax.named_scope("pool"):
            pooled = _pooled_from_rows(kc, pooled, layer, start_pos, t, cfg)
            mine = jax.lax.dynamic_index_in_dim(pooled, layer, axis=0, keepdims=False)

        def plain():
            if fresh_prefill:  # start_pos == 0: the fresh keys are all there is
                return (flash_attention_auto(q, k, v, cfg.attn_scale) if flash
                        else gqa_attention(q, k, v, mask, cfg.attn_scale))
            kw, vw = window(kc).astype(q.dtype), window(vc).astype(q.dtype)
            if on_cache:
                return flash_attention_chunk_auto(q, kw, vw, cfg.attn_scale, start_pos[0])
            return gqa_attention_hmajor(q, kw, vw, mask, cfg.attn_scale)

        def picked():
            with jax.named_scope("select"):
                keep = keep_blocks(block_scores(q, mine, positions + 1, cfg), positions + 1, cfg)
            return masked_attention(q, window(kc), window(vc),
                                    keep[..., : win // cfg.sparse_block], positions, cfg)

        if may_select:
            o = jax.lax.cond(jnp.max(positions) + 1 > cfg.sparse_dense_len, picked, plain)
        else:
            o = plain()
        return _attn_out(o, gate, p), (kc, vc, pooled, states)

    x, (kc, vc, pooled, states) = _layers(
        params, cfg, _embed(params, cfg, tokens), (k_cache.kv, v_cache.kv, pooled, states),
        {"lightning": lightning_, "sparse": sparse})
    from .llama import lm_head_logits

    at = None if logit_positions is None else jnp.maximum(logit_positions, 0)
    logits = lm_head_logits(params, cfg, x, at, t)
    # a row with no real position here (its prompt ended in an earlier chunk
    # of its group) has consumed nothing more
    with jax.named_scope("seq/linear"):
        seen = jnp.where(valid > 0, start_pos + valid, seen).astype(jnp.int32)
    return logits, WithState(kc, (pooled, seen), K_AXES), WithState(vc, (states,), V_AXES)


def forward_decode_paged(
    params: Params, cfg: ModelConfig, tokens: jax.Array,
    k_pool: WithState, v_pool: WithState,  # pools [NB, Ls, Hkv, T, D] + the slots' state
    tbl: jax.Array, start_pos: jax.Array, mesh=None,
):
    """``models.llama.forward_decode_paged``'s contract, one position a slot:
    the sparse layers write their row into the pool, write the pooled key the
    row completes, pick their blocks and walk them (the picked walk), the
    lightning layers update the state in place of the slots that hold a
    request, those whose row of ``tbl`` names a block. Row i of the batch IS
    slot i of the state."""
    from ..ops.paged_attention import paged_decode_attention_picked_auto

    del mesh
    b, w = tokens.shape
    if w != 1:
        raise NotImplementedError(
            "linear-attention models decode one position a step: a speculative "
            "bundle would advance the state past the drafts that are rejected, and "
            "the pool keeps no snapshot to go back to (SPEC_DECODE=0)")
    t = k_pool.shape[3]
    if cfg.sparse_block % t:
        raise ValueError(
            f"{cfg.arch}: KV_BLOCK_TOKENS={t} must divide the sparse block "
            f"({cfg.sparse_block}): a picked block is whole pool blocks")
    (pooled, seen), (states,) = k_pool.st, v_pool.st
    positions = start_pos[:, None]
    # one list for all the layers of the step (and of the burst: ``tbl`` is
    # the launch's, and no step changes it)
    with jax.named_scope("seq/linear"):
        live = ssm_scan.live_slots(table_rows_in_use(tbl))
        fresh = live.mask & (start_pos >= seen)

    def lightning_(h, p, carry, layer):
        kp, vp, pooled, states = carry
        out, states = lightning_step(h, p, cfg, states, layer, live, fresh, positions)
        return out, (kp, vp, pooled, states)

    def sparse(h, p, carry, layer):
        kp, vp, pooled, states = carry
        q, k, v, gate = _qkvg(h, p, cfg)
        kp = kv_pool_write_rows(kp, k, tbl, start_pos, layer)
        vp = kv_pool_write_rows(vp, v, tbl, start_pos, layer)
        with jax.named_scope("pool"):
            pooled = _pooled_from_pool(kp, pooled, tbl, start_pos, layer, live.mask, cfg)
            mine = jax.lax.dynamic_index_in_dim(pooled, layer, axis=0, keepdims=False)
        with jax.named_scope("select"):
            score = block_scores(q, mine, positions + 1, cfg)[:, :, 0]
            entries, count, last_len = picked_entries(score, start_pos, tbl, cfg, t)
        o = paged_decode_attention_picked_auto(
            q, kp, vp, entries, count, last_len, layer, cfg.attn_scale)
        return _attn_out(o, gate, p), (kp, vp, pooled, states)

    x, (kp, vp, pooled, states) = _layers(
        params, cfg, _embed(params, cfg, tokens), (k_pool.kv, v_pool.kv, pooled, states),
        {"lightning": lightning_, "sparse": sparse})
    from .llama import lm_head_logits

    logits = lm_head_logits(params, cfg, x, None, w)
    with jax.named_scope("seq/linear"):
        seen = jnp.where(fresh, start_pos + 1, seen).astype(jnp.int32)
    return logits, WithState(kp, (pooled, seen), K_AXES), WithState(vp, (states,), V_AXES)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Random small-scale init; the tree is what a loader of the family would
    build (``benchmark/references/sala.py param_shapes`` names it). ``decay``
    holds Lightning Attention's table at the lightning layers' published
    indices, as logits (``ops.lightning.decay_table``); the seeded weights of
    the benchmark draw it anew, by the reference's ``weight_gains``."""
    check(cfg)
    dt = jnp.dtype(cfg.dtype)
    keys = iter(jax.random.split(key, 32))

    def rand(*shape):
        return (jax.random.normal(next(keys), shape, jnp.float32) * 0.02).astype(dt)

    d, ff, hd = cfg.d_model, cfg.d_ff, cfg.head_dim
    ll, ls = cfg.n_lin_layers, cfg.n_kv_layers
    h, kd, vd = cfg.lin_v_heads, cfg.lin_v_heads * cfg.lin_k_dim, cfg.lin_v_heads * cfg.lin_v_dim

    def common(L: int) -> Params:
        return {"mix_norm": jnp.ones((L, d), dt), "ffn_norm": jnp.ones((L, d), dt),
                "w_gate": rand(L, d, ff), "w_up": rand(L, d, ff), "w_down": rand(L, ff, d)}

    blocks: Params = {}
    if ll:
        published = [cfg.stage_first_layer + i for i, kind in enumerate(cfg.layer_types)
                     if kind == "lightning"]
        blocks["linear"] = common(ll) | {
            "wq": rand(ll, d, kd), "wk": rand(ll, d, kd), "wv": rand(ll, d, vd),
            "wg": rand(ll, d, vd), "wo": rand(ll, vd, d),
            "q_norm": jnp.ones((ll, cfg.lin_k_dim), dt),
            "k_norm": jnp.ones((ll, cfg.lin_k_dim), dt),
            "out_norm": jnp.ones((ll, vd), dt),
            "decay": lightning.decay_table(
                published, h, cfg.stage_depth or cfg.n_layers).astype(dt)}
    if ls:
        blocks["attn"] = common(ls) | {
            "wq": rand(ls, d, 2 * cfg.n_heads * hd), "wk": rand(ls, d, cfg.n_kv_heads * hd),
            "wv": rand(ls, d, cfg.n_kv_heads * hd), "wo": rand(ls, cfg.n_heads * hd, d),
            "q_norm": jnp.ones((ls, hd), dt), "k_norm": jnp.ones((ls, hd), dt)}
    params: Params = {"embed": rand(cfg.vocab_size, d), "out_norm": jnp.ones((d,), dt),
                      "blocks": blocks}
    if not cfg.tie_embeddings:
        params["lm_head"] = rand(d, cfg.vocab_size)
    return params
