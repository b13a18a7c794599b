"""Causal flash attention (prefill) as a Pallas TPU kernel.

SURVEY.md §7 hard part #1: prefill TTFT needs attention that never
materializes the [T, S] score matrix in HBM. Online-softmax accumulation over
key tiles keeps everything in VMEM; one grid cell per (batch, q-head,
query-tile), with GQA folding (q head h reads kv head h // group).

Used for prefill only (start_pos == 0, keys are the just-computed [B, T]
block); decode keeps the fused XLA path, which is already memory-bound on
weights, not attention. Falls back to interpreter mode off-TPU so tests run
on the CPU backend (SURVEY.md §4.3).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# 512x1024 tiles: at 16k the 128x128 grid is 524k cells whose per-cell
# overhead dominated (measured ~350 -> ~230 ms/layer just from fewer cells);
# VMEM per cell stays ~4.5 MB. Short prefills clamp the blocks to T below.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024
_NEG_INF = -1e30


def chunk_block_multiple(quantized: bool, itemsize: int = 2) -> int:
    """Sublane multiple Mosaic requires of any cache-window extent the chunk
    kernels tile over: int8 codes need 32 rows, f32 8, bf16/f16 16. Both the
    chunk-continuation gate in models/llama.py and the paged-KV block-size
    clamp in serve/batcher.py use this floor, so a pool block is always a
    whole number of kernel tiles."""
    if quantized:
        return 32
    return 8 if itemsize >= 4 else 16


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale: float, block_q: int, block_k: int
):
    """One grid step = one (batch, q-head, q-tile, K-TILE). K/V arrive one
    [BK, D] tile per step — VMEM stays O(block) at any sequence length (the
    whole-K-per-cell layout capped prefill at ~8k tokens). Online-softmax
    state persists in scratch across the key-tile axis; causally-dead tiles
    skip compute (pl.when) and DMA (their index map revisits the previous
    tile, which the pipeline elides)."""
    qt, kt = pl.program_id(2), pl.program_id(3)
    d = q_ref.shape[-1]

    @pl.when(kt == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(kt * block_k <= (qt + 1) * block_q - 1)
    def _compute():
        # dots run in the INPUT dtype (bf16) with f32 accumulation — casting
        # operands to f32 first would route them through the ~4x slower f32
        # MXU path (measured: the whole 16k prefill dropped from ~7 s to
        # ~3 s when these dots went bf16). Softmax statistics stay f32.
        q = q_ref[0, 0]  # [BQ, D]
        k = k_ref[0, 0]  # [BK, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [BQ, BK] f32
        q_pos = qt * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_pos = kt * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, 0] * corr + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(kt == pl.num_programs(3) - 1)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "block_q", "block_k", "interpret"))
def flash_attention(
    q: jax.Array,  # [B, T, Hq, D]
    k: jax.Array,  # [B, T, Hkv, D]
    v: jax.Array,
    scale: float,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """Causal self-attention over a fresh [B, T] block. Returns q.dtype."""
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    # clamp to the sequence, then round up to the dtype's native sublane
    # tile (f32: 8 rows, bf16/f16: 16): Mosaic rejects ragged tile heights
    # on real TPU (invisible in CPU interpret-mode tests)
    mult = 8 if q.dtype.itemsize >= 4 else 16
    block_q = -(-min(block_q, max(t, mult)) // mult) * mult
    block_k = -(-min(block_k, max(t, mult)) // mult) * mult

    pad_q = (-t) % block_q
    pad_k = (-t) % block_k
    if pad_q or pad_k:
        # padded keys sit at positions >= t, which the causal mask removes
        # for every real query; padded query rows are sliced away below
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    tq, tk = q.shape[1], k.shape[1]

    # [B, H, T, D] layout: T/D in the trailing positions for Mosaic tiling
    qh = q.transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)

    def kv_map(bi, hi, qi, ki, g=group):
        # causal revisit-skip: tiles past this q-tile's last live key tile
        # remap to it, so their DMA is elided by the pipeline
        live = ((qi + 1) * block_q - 1) // block_k
        return (bi, hi // g, jnp.minimum(ki, live), 0)

    grid = (b, hq, tq // block_q, tk // block_k)
    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d), kv_map),
            pl.BlockSpec((1, 1, block_k, d), kv_map),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, tq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",  # constant: the custom call's name in a trace
    )(qh, kh, vh)
    return out.transpose(0, 2, 1, 3)[:, :t]


def flash_attention_auto(q, k, v, scale: float) -> jax.Array:
    """flash_attention with interpreter fallback off-TPU (tests on the CPU
    backend run the same kernel logic through the Pallas interpreter)."""
    interpret = jax.default_backend() != "tpu"
    return flash_attention(q, k, v, scale, interpret=interpret)


# ---------------------------------------------------------------------------
# cache-backed chunk attention (chunked prefill continuation)
# ---------------------------------------------------------------------------


def _flash_chunk_kernel(
    start_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale: float, block_q: int, block_k: int
):
    """One grid step = one (batch, q-head, q-tile, K-TILE) of CHUNK
    CONTINUATION attention: queries sit at positions [start, start+C) while
    keys/values are the cache slab [0, KW) — history below ``start`` fully
    visible, the chunk itself causal, anything above masked. ``start`` is a
    scalar-prefetch operand, so one compiled program serves every chunk
    offset (a static start would recompile the 8B program per chunk)."""
    qt, kt = pl.program_id(2), pl.program_id(3)
    start = start_ref[0]

    @pl.when(kt == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(kt * block_k <= start + (qt + 1) * block_q - 1)
    def _compute():
        q = q_ref[0, 0]  # [BQ, D]
        k = k_ref[0, 0]  # [BK, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        q_pos = start + qt * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_pos = kt * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, 0] * corr + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(kt == pl.num_programs(3) - 1)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "block_q", "block_k", "interpret"))
def flash_attention_chunk(
    q: jax.Array,  # [B, C, Hq, D] — queries at positions [start, start+C)
    k: jax.Array,  # [B, Hkv, KW, D] — cache slab (heads-major, as stored)
    v: jax.Array,
    scale: float,
    start: jax.Array,  # int32 scalar, shared by every row (uniform starts)
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Chunked-prefill continuation attention without the [C, KW] f32 score
    matrix (the dense fallback materializes ~1 GB/layer at a 4.6k window —
    most of a chunk's wall time). Keys at positions >= start+C (junk beyond
    the written prefix) are masked by causality since every query position
    is < start+C. Rows whose prompt is shorter than ``start`` (pad chunks
    of a batched group admit) produce finite junk that the caller's
    end-chunk logit select discards."""
    b, c, hq, d = q.shape
    hkv, kw = k.shape[1], k.shape[2]
    group = hq // hkv
    mult = 8 if q.dtype.itemsize >= 4 else 16
    block_q = -(-min(block_q, max(c, mult)) // mult) * mult
    pad_q = (-c) % block_q
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    while kw % block_k and block_k > mult:
        block_k //= 2
    if kw % block_k:
        raise ValueError(f"cache window {kw} not tileable by {block_k}")
    qh = q.transpose(0, 2, 1, 3)  # [B, Hq, Cp, D]

    def q_map(bi, hi, qi, ki, start_ref):
        return (bi, hi, qi, 0)

    def kv_map(bi, hi, qi, ki, start_ref, g=group):
        # causal revisit-skip: tiles past the last live key tile for this
        # q tile remap to it (their DMA is elided by the pipeline)
        live = (start_ref[0] + (qi + 1) * block_q - 1) // block_k
        return (bi, hi // g, jnp.minimum(ki, live), 0)

    grid = (b, hq, qh.shape[2] // block_q, kw // block_k)
    kernel = functools.partial(
        _flash_chunk_kernel, scale=scale, block_q=block_q, block_k=block_k
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), q_map),
            pl.BlockSpec((1, 1, block_k, d), kv_map),
            pl.BlockSpec((1, 1, block_k, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qh.shape, q.dtype),
        interpret=interpret,
        name="flash_attention_chunk",
    )(jnp.reshape(start, (1,)).astype(jnp.int32), qh, k, v)
    return out.transpose(0, 2, 1, 3)[:, :c]


def flash_attention_chunk_auto(q, k, v, scale: float, start) -> jax.Array:
    interpret = jax.default_backend() != "tpu"
    return flash_attention_chunk(q, k, v, scale, start, interpret=interpret)


# ---------------------------------------------------------------------------
# cache-backed chunk attention over the QUANTIZED cache (int8 KV serving)
# ---------------------------------------------------------------------------


def _flash_chunk_kvq_kernel(
    start_ref, q_ref, kq_ref, ks_ref, vq_ref, vs_ref, o_ref,
    acc_ref, m_ref, l_ref,
    *, scale: float, block_q: int, block_k: int, group: int
):
    """flash_attention_chunk over int8 KV tiles: codes dequantize per tile
    IN VMEM (k = kq * ks[:, None] in the compute dtype), so the int8 slab
    streams from HBM at half the bf16 bytes and the full-window dequant
    transient the XLA path materializes per layer per chunk (the r4 O(T^2)
    HBM tail at 16k) never exists.

    Scale tiles arrive as [1, Hkv, block_k] (ALL kv heads per cell —
    Mosaic requires the block's sublane dim to divide by 8 or equal the
    array dim, which a single-head (1, 1, bk) block violates); the cell's
    own head is selected here. The extra scale DMA is Hkv x 4 bytes/slot,
    noise next to the [bk, D] codes."""
    qt, kt = pl.program_id(2), pl.program_id(3)
    h_kv = pl.program_id(1) // group
    start = start_ref[0]

    @pl.when(kt == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(kt * block_k <= start + (qt + 1) * block_q - 1)
    def _compute():
        q = q_ref[0, 0]  # [BQ, D] (bf16)
        # dequant in f32, cast after: Mosaic only supports the [BK] -> [BK, 1]
        # minor-dim insertion for 32-bit vectors (bf16 broadcast here fails
        # to lower); the cast lands the MXU dot back in bf16
        k = (kq_ref[0, 0].astype(jnp.float32)
             * ks_ref[0, h_kv].astype(jnp.float32)[:, None]).astype(q.dtype)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        q_pos = start + qt * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_pos = kt * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, 0] * corr + jnp.sum(p, axis=1)
        v = (vq_ref[0, 0].astype(jnp.float32)
             * vs_ref[0, h_kv].astype(jnp.float32)[:, None]).astype(q.dtype)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v,
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(kt == pl.num_programs(3) - 1)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "block_q", "block_k", "interpret"))
def flash_attention_chunk_kvq(
    q: jax.Array,   # [B, C, Hq, D] — queries at positions [start, start+C)
    kq: jax.Array,  # [B, Hkv, KW, D] int8 codes (cache slab, heads-major)
    ks: jax.Array,  # [B, Hkv, KW] per-slot scales
    vq: jax.Array,
    vs: jax.Array,
    scale: float,
    start: jax.Array,  # int32 scalar, shared by every row (uniform starts)
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Chunk-continuation attention reading the int8 KV cache directly.
    Same math/masking as flash_attention_chunk; the dequantized k/v exist
    only tile-by-tile in VMEM. int8 tiles need a 32-row sublane multiple,
    so block_k stays a multiple of 32 (KW is a pow2 window >= 512 in
    serving, so the halving loop never goes below it)."""
    b, c, hq, d = q.shape
    hkv, kw = kq.shape[1], kq.shape[2]
    group = hq // hkv
    mult = 8 if q.dtype.itemsize >= 4 else 16
    block_q = -(-min(block_q, max(c, mult)) // mult) * mult
    pad_q = (-c) % block_q
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    while kw % block_k and block_k > 32:
        block_k //= 2
    if kw % block_k:
        raise ValueError(f"cache window {kw} not tileable by int8 block {block_k}")
    qh = q.transpose(0, 2, 1, 3)  # [B, Hq, Cp, D]

    def q_map(bi, hi, qi, ki, start_ref):
        return (bi, hi, qi, 0)

    def kv_map(bi, hi, qi, ki, start_ref, g=group):
        live = (start_ref[0] + (qi + 1) * block_q - 1) // block_k
        return (bi, hi // g, jnp.minimum(ki, live), 0)

    def s_map(bi, hi, qi, ki, start_ref):
        # scale tiles ride the same causal revisit-skip as their codes;
        # the head axis is blocked whole (see kernel docstring)
        live = (start_ref[0] + (qi + 1) * block_q - 1) // block_k
        return (bi, 0, jnp.minimum(ki, live))

    grid = (b, hq, qh.shape[2] // block_q, kw // block_k)
    kernel = functools.partial(
        _flash_chunk_kvq_kernel, scale=scale, block_q=block_q, block_k=block_k,
        group=group,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), q_map),
            pl.BlockSpec((1, 1, block_k, d), kv_map),
            pl.BlockSpec((1, hkv, block_k), s_map),
            pl.BlockSpec((1, 1, block_k, d), kv_map),
            pl.BlockSpec((1, hkv, block_k), s_map),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qh.shape, q.dtype),
        interpret=interpret,
        name="flash_attention_chunk_kvq",
    )(jnp.reshape(start, (1,)).astype(jnp.int32), qh, kq, ks, vq, vs)
    return out.transpose(0, 2, 1, 3)[:, :c]


def flash_attention_chunk_kvq_auto(q, kq, ks, vq, vs, scale: float, start) -> jax.Array:
    interpret = jax.default_backend() != "tpu"
    return flash_attention_chunk_kvq(q, kq, ks, vq, vs, scale, start,
                                     interpret=interpret)
