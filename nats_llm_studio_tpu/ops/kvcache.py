"""Quantized KV cache: int8 codes + per-(position, head) scales.

Batched decode is HBM-bound (SURVEY.md §7 hard part #5); after int8 weights
(ops/wquant.py) the next largest per-step read is the KV cache — at Llama-3-8B
batch 48 x window 512 it is ~3 GB/step of bf16. Storing K/V as int8 halves
that traffic AND halves cache capacity per slot, which is what lets the batch
grow past the b48 HBM frontier (every extra row is ~free throughput on a
memory-bound step).

Design: symmetric absmax int8 over the head_dim axis — one f32 scale per
(batch, layer, kv-head, position). Dequantization never materializes bf16
slabs: attention folds the scales OUTSIDE the dots, so the MXU reads int8
codes directly (XLA fuses convert(s8->bf16) into the dot operand read, the
same mechanism that makes weight-only int8 pay off):

    scores[b,h,t,s] = (q . codes[s]) * k_scale[s]      (scale on the S axis)
    out[b,t,d]      = sum_s (p[s] * v_scale[s]) codes[s]  (fold into probs)

``KVQ`` is a registered pytree, so a quantized cache flows through jit /
scan / donation / shard_map exactly like the bf16 arrays it replaces; the
scan's leading-axis slicing and dynamic_update_slice run per leaf via the
helpers below.

The reference reaches the same capability through llama.cpp's quantized KV
options inside LM Studio (/root/reference/README.md:3-7); here it is a
first-class device representation selected by ``ModelConfig.kv_quant``.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp


@jax.tree_util.register_dataclass
@dataclass
class KVQ:
    """Quantized cache tensor: ``value ~= q * s[..., None]``.

    q: int8 codes, the cache layout [..., S, D]
    s: f32 scales [..., S] (one per position per kv-head)
    """

    q: jax.Array
    s: jax.Array

    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim


def is_quantized(cache) -> bool:
    return isinstance(cache, KVQ)


def kv_zeros(shape, sdtype=jnp.float32) -> KVQ:
    """Zeroed quantized cache (codes 0 x any scale = 0; scales init to 1 so
    never-written positions stay harmless)."""
    return KVQ(q=jnp.zeros(shape, jnp.int8), s=jnp.ones(shape[:-1], sdtype))


def quantize_rows(x: jax.Array) -> KVQ:
    """Symmetric absmax int8 over the last (head_dim) axis."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    s = amax / 127.0
    safe = jnp.where(s == 0, 1.0, s)
    codes = jnp.clip(jnp.round(xf / safe), -127, 127).astype(jnp.int8)
    return KVQ(q=codes, s=safe[..., 0])


def kv_update_slice(cache, upd, idx):
    """dynamic_update_slice on a bf16 cache, or per-leaf on a KVQ (the
    update rows are quantized on write; ``idx`` indexes the CODES layout,
    the scale write drops the trailing D index)."""
    if not is_quantized(cache):
        return jax.lax.dynamic_update_slice(cache, upd.astype(cache.dtype), idx)
    uq = quantize_rows(upd)
    return KVQ(
        q=jax.lax.dynamic_update_slice(cache.q, uq.q, idx),
        s=jax.lax.dynamic_update_slice(cache.s, uq.s, idx[:-1]),
    )


def kv_copy_slice(dst, src, idx):
    """Write an ALREADY-QUANTIZED block (e.g. a prefilled row cache) into a
    larger cache at ``idx`` (codes layout indices)."""
    if not is_quantized(dst):
        return jax.lax.dynamic_update_slice(dst, src.astype(dst.dtype), idx)
    return KVQ(
        q=jax.lax.dynamic_update_slice(dst.q, src.q, idx),
        s=jax.lax.dynamic_update_slice(dst.s, src.s, idx[:-1]),
    )


def kv_slice(cache, idx, sizes):
    """dynamic_slice in the codes layout; per-leaf on a KVQ."""
    if not is_quantized(cache):
        return jax.lax.dynamic_slice(cache, idx, sizes)
    return KVQ(
        q=jax.lax.dynamic_slice(cache.q, idx, sizes),
        s=jax.lax.dynamic_slice(cache.s, idx[:-1], sizes[:-1]),
    )


def kv_nbytes(cache) -> int:
    """Device bytes a cache (bf16 array or KVQ pytree) occupies — the
    prefix cache's HBM accounting unit. 0 for None."""
    if cache is None:
        return 0
    if is_quantized(cache):
        return cache.q.size * cache.q.dtype.itemsize + cache.s.size * cache.s.dtype.itemsize
    return cache.size * cache.dtype.itemsize


def host_kv_nbytes(leaf) -> int:
    """Host bytes of one transferred/demoted KV leaf: an ndarray, a KVQ
    pytree, or the wire-normalized ``(codes, scales)`` tuple
    (serve/kv_transfer.py) — the host-tier budget's accounting unit."""
    if leaf is None:
        return 0
    if isinstance(leaf, tuple):
        q, s = leaf
        return int(q.size) * q.dtype.itemsize + int(s.size) * s.dtype.itemsize
    if is_quantized(leaf):
        return (
            int(leaf.q.size) * leaf.q.dtype.itemsize
            + int(leaf.s.size) * leaf.s.dtype.itemsize
        )
    return int(leaf.size) * leaf.dtype.itemsize


def kv_gather_block(cache, row: int, start: int, length: int):
    """Copy one row's S-axis block [start, start+length) out of a
    [B, L, H, S, D]-layout cache as a fresh [1, L, H, length, D] array (or
    KVQ pair). Static Python slicing — eager, no compiled program — so the
    prefix cache can harvest blocks from a transient row cache before the
    donating finish-admit call consumes it."""
    if not is_quantized(cache):
        return jnp.copy(cache[row : row + 1, :, :, start : start + length, :])
    return KVQ(
        q=jnp.copy(cache.q[row : row + 1, :, :, start : start + length, :]),
        s=jnp.copy(cache.s[row : row + 1, :, :, start : start + length]),
    )


def kv_roll_s(cache, shift, s_axis: int):
    """jnp.roll along the sequence axis (ring alignment / compaction)."""
    if not is_quantized(cache):
        return jnp.roll(cache, shift, axis=s_axis)
    return KVQ(
        q=jnp.roll(cache.q, shift, axis=s_axis),
        s=jnp.roll(cache.s, shift, axis=s_axis),
    )


# -- paged block pool ---------------------------------------------------------
#
# The pool layout is [NB, L, Hkv, T, D] (codes) / [NB, L, Hkv, T] (scales):
# one leading axis of fixed-size blocks of T positions, shared by every live
# slot, the prefix cache, and spec decode.  A slot's logical [B, L, Hkv, S, D]
# cache is the gather of its block table along the leading axis; after a
# decode burst only the touched blocks are scattered back.  Block id 0 is the
# null block (junk pad) — reads from it are masked by the causal mask and
# writes to it are discarded state, so duplicates of id 0 in a scatter are
# benign even though jnp scatter leaves duplicate-index order undefined.


def table_rows_in_use(tbl):
    """[B] bool from a block table [B, NB]: the rows that name a block other
    than the null block, i.e. the slots that hold a request. A numpy table
    gives a numpy mask and a traced one a traced mask: the host counts its
    slots by the rule the device lists them by."""
    return (tbl != 0).any(axis=1)


def kv_pool_zeros(shape, dtype=None, quant: bool = False):
    """A zeroed pool leaf-set: bf16/f32 array or KVQ pair, [NB, L, H, T, D]."""
    if quant:
        return kv_zeros(shape)
    return jnp.zeros(shape, dtype if dtype is not None else jnp.bfloat16)


def _pool_take(a, tbl):
    """Gather [B, nb] block ids into a contiguous per-row view.

    a: [NB, L, H, T, ...] pool leaf;  tbl: [B, nb] int32 block ids
    returns [B, L, H, nb*T, ...] — the S axis is the concatenation of the
    row's blocks in table order.
    """
    b, nb = tbl.shape
    v = jnp.take(a, tbl.reshape(-1), axis=0).reshape((b, nb) + a.shape[1:])
    v = jnp.moveaxis(v, 1, 3)  # [B, L, H, nb, T, ...]
    return v.reshape(v.shape[:3] + (nb * a.shape[3],) + a.shape[4:])


def kv_pool_gather_view(pool, tbl):
    """Materialize the [B, L, H, nb*T, D] cache view a block table describes
    (per leaf on KVQ).  The view feeds the existing positional ``forward``
    path unchanged: its S extent IS the attention window."""
    if not is_quantized(pool):
        return _pool_take(pool, tbl)
    return KVQ(q=_pool_take(pool.q, tbl), s=_pool_take(pool.s, tbl))


def _pool_blocks_of_view(v, n_blocks, block_tokens):
    """[B, L, H, nb*T, ...] -> [B, nb, L, H, T, ...] (split S into blocks)."""
    blk = v.reshape(v.shape[:3] + (n_blocks, block_tokens) + v.shape[4:])
    return jnp.moveaxis(blk, 3, 1)


def kv_pool_scatter_view(pool, view, tbl, vb):
    """Write back the touched blocks of a gathered view.

    vb: [B, NTB] indices INTO THE VIEW's block axis (clipped to [0, nb));
    the pool block ids come from ``take_along_axis(tbl, vb)``.  Rows never
    share writable blocks (CoW guarantees it), so the only duplicate ids in
    the flattened scatter are null-block pads — benign junk writes.
    """
    b, nb = tbl.shape
    bids = jnp.take_along_axis(tbl, vb, axis=1).reshape(-1)  # [B*NTB]

    def scat(p, v):
        t = p.shape[3]
        blk = _pool_blocks_of_view(v, nb, t)  # [B, nb, L, H, T, ...]
        idx = vb.reshape(vb.shape + (1,) * (blk.ndim - 2))
        touched = jnp.take_along_axis(blk, idx, axis=1)  # [B, NTB, L, H, T, ...]
        return p.at[bids].set(touched.reshape((-1,) + touched.shape[2:]))

    if not is_quantized(pool):
        return scat(pool, view)
    return KVQ(q=scat(pool.q, view.q), s=scat(pool.s, view.s))


def kv_pool_write_row(pool, row, bids):
    """Write one prefilled row cache into the pool's blocks ``bids``.

    row: [1, L, H, S', D] (already quantized under KVQ); bids: [nblk] int32.
    S' < T writes a partial leading block via DUS; otherwise S' must be a
    multiple of T and every block scatters in one op.  Pad bids with 0 (the
    null block) when the row has fewer real blocks than ``len(bids)``.
    """

    def put(p, r):
        t = p.shape[3]
        s = r.shape[3]
        if s <= t:
            start = (bids[0],) + (jnp.int32(0),) * (p.ndim - 1)
            return jax.lax.dynamic_update_slice(p, r.astype(p.dtype), start)
        if s % t:
            raise ValueError(f"row length {s} not a multiple of block size {t}")
        blk = r[0].reshape(r.shape[1:3] + (s // t, t) + r.shape[4:])
        blk = jnp.moveaxis(blk, 2, 0)  # [nblk, L, H, T, ...]
        return p.at[bids].set(blk.astype(p.dtype))

    if not is_quantized(pool):
        return put(pool, row)
    return KVQ(q=put(pool.q, row.q), s=put(pool.s, row.s))


def kv_pool_write_rows(pool, rows, tbl, pos, layer):
    """Scatter W fresh [Hkv, D] rows per slot straight into the pool at the
    slot's logical positions pos..pos+W-1 (write-then-attend for the Pallas
    paged-decode kernel, ops/paged_attention.py — no gather view exists on
    that path, so fresh rows cannot ride a view scatter-back).

    rows: [B, W, Hkv, D] raw activations (quantized on write under KVQ);
    tbl: [B, NB] block ids; pos: [B] int32; layer: int32 scalar (traced).
    Touched indices past a slot's table resolve to the null block (id 0);
    duplicate junk writes there are benign (pool contract above).
    """
    w = rows.shape[1]
    offs = pos[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]  # [B, W]

    def put(p, r):
        # every index but the minor-most axis is explicit, so each update
        # is one contiguous row of the pool as it lies in memory. Leaving
        # the head axis a slice makes the scatter's window [Hkv, D]; the
        # TPU compiler then re-lays the WHOLE pool out heads-minor for the
        # scatter and back for the attention kernel — two pool-sized copies
        # per layer per step, and a pool-sized temporary next to the
        # weights.
        t = p.shape[3]
        vb = jnp.clip(offs // t, 0, tbl.shape[1] - 1)
        bids = jnp.take_along_axis(tbl, vb, axis=1)  # [B, W]
        heads = jnp.arange(p.shape[2], dtype=jnp.int32)
        return p.at[
            bids[:, :, None], layer, heads[None, None, :], (offs % t)[:, :, None]
        ].set(r.astype(p.dtype))

    if not is_quantized(pool):
        return put(pool, rows)
    rq = quantize_rows(rows)
    return KVQ(q=put(pool.q, rq.q), s=put(pool.s, rq.s))


def kv_pool_copy_block(pool, dst, src):
    """Copy-on-write: duplicate block ``src`` into ``dst`` (traced scalars)."""

    def cp(p):
        sizes = (1,) + p.shape[1:]
        zeros = (jnp.int32(0),) * (p.ndim - 1)
        blk = jax.lax.dynamic_slice(p, (src,) + zeros, sizes)
        return jax.lax.dynamic_update_slice(p, blk, (dst,) + zeros)

    if not is_quantized(pool):
        return cp(pool)
    return KVQ(q=cp(pool.q), s=cp(pool.s))


def kv_pool_read_blocks(pool, bids):
    """Gather ``bids`` [nblk] into a [1, L, H, nblk*T, D] row-cache-shaped
    chunk (per leaf on KVQ) — the partial-prefix-hit path uses this to seed
    a transient row cache from cached pool blocks."""

    def rd(a):
        v = jnp.take(a, bids, axis=0)  # [nblk, L, H, T, ...]
        v = jnp.moveaxis(v, 0, 2)  # [L, H, nblk, T, ...]
        v = v.reshape(v.shape[:2] + (v.shape[2] * v.shape[3],) + v.shape[4:])
        return v[None]  # [1, L, H, nblk*T, ...]

    if not is_quantized(pool):
        return rd(pool)
    return KVQ(q=rd(pool.q), s=rd(pool.s))


# -- recurrent state beside the KV ---------------------------------------------
#
# A family with state-space layers (models/ssm_hybrid.py) keeps, beside the
# KV of its attention layers, a per-slot state that no block table describes:
# it is indexed by SLOT. ``WithState`` pairs the two so that whatever carries
# a cache (a pool, a transient row cache, one row of either) carries the state
# with it: the batcher hands the pair through its programs as it hands K and V.


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["kv", "st"], meta_fields=["axes"])
@dataclass
class WithState:
    """A cache with per-row state beside it.

    kv: the KV leaf, pool [NB, L, H, T, D] or rows [B, L, H, S, D]
    st: tuple of arrays: a slot's (pool) or a row's (transient) recurrent
        state; row i of a pool's ``st`` belongs to slot i
    axes: where each leaf of ``st`` has its row axis (static). A leaf that a
        layer scan updates a layer at a time is laid out layer-major
        ([layers, rows, ...], axis 1): the device would re-lay it out so on
        every dispatch otherwise. The convolution tails of the state-space and
        gated-delta-rule layers lie a tap a plane under the layer
        ([layers, K, rows, C], axis 2): a step works on a tap of all the rows.
    """

    kv: jax.Array
    st: tuple
    axes: tuple = ()

    @property
    def shape(self):
        return self.kv.shape

    @property
    def ndim(self):
        return self.kv.ndim


def has_state(cache) -> bool:
    return isinstance(cache, WithState)


def state_row(cache: WithState, i) -> tuple:
    """Row ``i`` (traced) of every state leaf, one row long on its row axis."""
    return tuple(jax.lax.dynamic_slice_in_dim(a, i, 1, axis=ax)
                 for a, ax in zip(cache.st, cache.axes))


def state_write_row(cache: WithState, row: tuple, slot) -> tuple:
    """``cache.st`` with the one-row leaves ``row`` written at row ``slot``
    (traced)."""
    return tuple(jax.lax.dynamic_update_slice_in_dim(a, r.astype(a.dtype), slot, axis=ax)
                 for a, r, ax in zip(cache.st, row, cache.axes))
