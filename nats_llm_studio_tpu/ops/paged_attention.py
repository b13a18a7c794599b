"""Paged-attention decode as a Pallas TPU kernel.

vLLM-style PagedAttention for the decode path (SURVEY.md §7 hard part #2,
ROADMAP item 1): one grid cell per (slot, kv-head, pool-block), reading each
slot's block table directly from scalar-prefetch SMEM — the kernel walks
``[NB, L, Hkv, T, D]`` pool storage block-by-block in VMEM, dequantizes int8
KVQ codes per tile, and runs online softmax across blocks. This removes the
two costs of the XLA fallback in serve/batcher.py:

- ``kv_pool_gather_view`` materializes every slot's live window as a dense
  [B, L, Hkv, W, D] copy per decode step (HBM round-trip proportional to
  context, not to the one new token);
- the pow2 window ladder re-jits ``decode_pos_paged`` per (bucket, window)
  pair as contexts grow.

Here the grid's block axis spans the WHOLE table width (static = max_seq/T),
so one compiled program serves every context length: blocks past a slot's
live window skip compute (``pl.when``) and their DMA is elided because the
index map revisits the last live block (the same trick as the causal
revisit-skip in ops/flash_attention.py).

Queries arrive as the slot's GQA group x query-width bundle: decode is
W == 1, speculative verify passes the draft bundle W == k+1 — one kernel,
one compiled program per width. Off-TPU the kernel runs in interpreter mode
(bit-level tests on the CPU backend); ``paged_decode_eligible`` gates the
auto-downshift to the XLA path for shapes Mosaic cannot tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kvcache import is_quantized

_NEG_INF = -1e30


def paged_decode_eligible(
    t: int, d: int, itemsize: int, quantized: bool, hkv: int = 1, tp: int = 1
) -> bool:
    """Whether the Pallas paged-decode kernel can serve this pool layout on
    a real TPU. The block-token extent T is the sublane dim of every K/V
    tile (f32 8 rows, bf16 16), the head_dim D is the lane dim (128
    multiple), and under tensor parallelism each shard must own whole KV
    heads. int8 KVQ codes pack 32 rows to a native tile, but every K/V
    block spans the pool's WHOLE [T, D] minor plane, which Mosaic accepts
    below the native tile: codes at the default KV_BLOCK_TOKENS=16 compile
    for a v5e (tests/test_tpu_compile.py) and agree with the XLA path on
    the chip (PERF.md, PR 21), so TPU_KV_QUANT=int8 keeps the kernel at
    the default block size. Anything else downshifts to the XLA path."""
    sub = 8 if itemsize >= 4 and not quantized else 16
    return t % sub == 0 and d % 128 == 0 and hkv % tp == 0


def _paged_kernel(
    tbl_ref, pos_ref, layer_ref, q_ref, *refs,
    scale: float, t: int, group: int, w: int, quantized: bool
):
    """One grid step = one (slot, kv-head, POOL-BLOCK). Scratch carries the
    online-softmax state across the block axis; q rows are the slot's GQA
    bundle (row r = query-offset r//group within the W-wide bundle, q-head
    r%group within the group), so the causal frontier is per-row:
    ``key_pos <= pos + r//group``. Rows written this step (write-then-
    attend in models/llama.py) are already in the pool, so the frontier
    includes them. Dead blocks (j past the slot's last live block) skip
    compute; their index maps revisit the last live block so the DMA is
    elided. Slots whose table is unallocated read the null block (id 0) and
    produce finite junk the caller discards — the same contract as the XLA
    gather-view path."""
    if quantized:
        kq_ref, ks_ref, vq_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = refs
    else:
        k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
    b, h, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    pos = pos_ref[b]
    last = jnp.minimum(jnp.maximum(pos + w - 1, 0) // t, pl.num_programs(2) - 1)
    rows = q_ref.shape[-2]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j <= last)
    def _compute():
        q = q_ref[0, 0]  # [rows, D]
        if quantized:
            # dequant in f32, cast after: Mosaic's minor-dim [T] -> [T, 1]
            # insertion only lowers for 32-bit vectors (ops/flash_attention.py)
            k = (kq_ref[0, 0, 0].astype(jnp.float32)
                 * ks_ref[0, 0, h].astype(jnp.float32)[:, None]).astype(q.dtype)
            v = (vq_ref[0, 0, 0].astype(jnp.float32)
                 * vs_ref[0, 0, h].astype(jnp.float32)[:, None]).astype(q.dtype)
        else:
            k = k_ref[0, 0, 0].astype(q.dtype)  # [T, D]
            v = v_ref[0, 0, 0].astype(q.dtype)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [rows, T] f32
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, t), 0)
        key_pos = j * t + jax.lax.broadcasted_iota(jnp.int32, (rows, t), 1)
        s = jnp.where(key_pos <= pos + row // group, s, _NEG_INF)
        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, 0] * corr + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v,
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,      # [B, W, Hq, D] — queries at positions pos..pos+W-1
    k_pool,            # [NBp, L, Hkv, T, D] array, or KVQ codes+scales
    v_pool,
    tbl: jax.Array,    # [B, NB] int32 block ids (NB static = max table width)
    pos: jax.Array,    # [B] int32 — first query position per slot
    layer,             # int32 scalar (a traced lax.scan index is fine)
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """Attention for W new tokens per slot against the slot's ENTIRE paged
    history, read block-by-block straight from the pool. Returns
    [B, W, Hq, D] in q.dtype. The caller must have scattered the W new K/V
    rows into the pool first (write-then-attend); the kernel's causal mask
    then covers them exactly.

    The grid block axis is ``tbl.shape[1]`` — STATIC, so the compiled
    program is shared by every context length (dead blocks cost one elided
    grid step each, not a recompile). Per-block work is [rows, T] x [T, D];
    rows = GQA group x W (padded to the sublane multiple)."""
    b, w, hq, d = q.shape
    quantized = is_quantized(k_pool)
    kq = k_pool.q if quantized else k_pool
    hkv, t = kq.shape[2], kq.shape[3]
    group = hq // hkv
    nb = tbl.shape[1]
    rows = group * w
    mult = 8 if q.dtype.itemsize >= 4 else 16
    rows_p = -(-rows // mult) * mult

    # [B, Hkv, group*W, D]: row r = (query offset r//group, group lane
    # r%group) — head-major GQA fold, query offset outermost per group
    qh = q.reshape(b, w, hkv, group, d).transpose(0, 2, 1, 3, 4)
    qh = qh.reshape(b, hkv, rows, d)
    if rows_p != rows:
        qh = jnp.pad(qh, ((0, 0), (0, 0), (0, rows_p - rows), (0, 0)))

    def q_map(bi, hi, ji, tbl_ref, pos_ref, layer_ref):
        return (bi, hi, 0, 0)

    def kv_map(bi, hi, ji, tbl_ref, pos_ref, layer_ref):
        # dead-block revisit-skip: blocks past the slot's live frontier
        # remap to the last live block, eliding their DMA
        last = jnp.minimum(jnp.maximum(pos_ref[bi] + w - 1, 0) // t, nb - 1)
        return (tbl_ref[bi, jnp.minimum(ji, last)], layer_ref[0], hi, 0, 0)

    def s_map(bi, hi, ji, tbl_ref, pos_ref, layer_ref):
        # scale tiles block the whole head axis (a (.., 1, T) block violates
        # Mosaic's sublane rule); the cell's own head is picked in-kernel
        last = jnp.minimum(jnp.maximum(pos_ref[bi] + w - 1, 0) // t, nb - 1)
        return (tbl_ref[bi, jnp.minimum(ji, last)], layer_ref[0], 0, 0)

    if quantized:
        in_specs = [
            pl.BlockSpec((1, 1, rows_p, d), q_map),
            pl.BlockSpec((1, 1, 1, t, d), kv_map),
            pl.BlockSpec((1, 1, hkv, t), s_map),
            pl.BlockSpec((1, 1, 1, t, d), kv_map),
            pl.BlockSpec((1, 1, hkv, t), s_map),
        ]
        operands = (kq, k_pool.s, v_pool.q, v_pool.s)
    else:
        in_specs = [
            pl.BlockSpec((1, 1, rows_p, d), q_map),
            pl.BlockSpec((1, 1, 1, t, d), kv_map),
            pl.BlockSpec((1, 1, 1, t, d), kv_map),
        ]
        operands = (k_pool, v_pool)

    kernel = functools.partial(
        _paged_kernel, scale=scale, t=t, group=group, w=w, quantized=quantized
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hkv, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, rows_p, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((rows_p, d), jnp.float32),
            pltpu.VMEM((rows_p, 128), jnp.float32),
            pltpu.VMEM((rows_p, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows_p, d), q.dtype),
        interpret=interpret,
        # a constant: it is the custom call's name in a device trace and part
        # of the program's bytes, so of its compile-cache key
        name="paged_decode_attention",
    )(
        tbl.astype(jnp.int32),
        jnp.asarray(pos, jnp.int32).reshape(b),
        jnp.asarray(layer, jnp.int32).reshape(1),
        qh, *operands,
    )
    out = out[:, :, :rows].reshape(b, hkv, w, group, d)
    return out.transpose(0, 2, 1, 3, 4).reshape(b, w, hq, d)


def paged_decode_attention_auto(q, k_pool, v_pool, tbl, pos, layer,
                                scale: float) -> jax.Array:
    """paged_decode_attention with interpreter fallback off-TPU (the CPU
    backend runs the same kernel logic through the Pallas interpreter, so
    the equivalence suite exercises real kernel code paths)."""
    interpret = jax.default_backend() != "tpu"
    return paged_decode_attention(q, k_pool, v_pool, tbl, pos, layer, scale,
                                  interpret=interpret)
