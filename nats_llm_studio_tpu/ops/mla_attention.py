"""Absorbed latent-attention (MLA) decode: the XLA form and a Pallas TPU
kernel over the paged pool.

In the absorbed form a head's query lives in latent space
(``q~_h = W_uk_h q_nope_h``, ``models/mla_moe.py``), so every head scores the
SAME cached rows: the normalised latent ``c`` (kv_lora_rank wide) and the one
rotated key ``k_r`` all heads share. The values are the latents again:

    score_h[s] = (q~_h . c[s] + q_rope_h . k_r[s]) * scale
    o_lat_h    = sum_s softmax(score_h)[s] c[s]          (W_uv applied by the caller)

That is multi-query attention with one "kv head" of two unlike widths whose
V is its own K's first part. ``mla_paged_decode_attention`` walks a slot's
block table exactly as ``ops/paged_attention.py`` does (PR 26's kernel: one
grid cell a slot, runs of k consecutive table entries copied into a double
buffer, the trip count the slot's live runs), with every query head of the
slot in the cell: a run is read from HBM once for all 32 heads.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import _NEG_INF, _run_blocks


def mla_absorbed_attention(
    qt: jax.Array,         # [B, T, H, R] queries in latent space
    q_rope: jax.Array,     # [B, T, H, dr]
    c_win: jax.Array,      # [B, S, R] cached latents (a window or a gathered view)
    kr_win: jax.Array,     # [B, S, dr] cached rotary keys
    positions: jax.Array,  # [B, T]: query t sees cache index <= positions[b, t]
    scale: float,
) -> jax.Array:
    """The XLA path: [B, T, H, R] latent-space outputs, f32 softmax."""
    s = jnp.einsum("bthr,bsr->bhts", qt, c_win, preferred_element_type=jnp.float32)
    s = s + jnp.einsum("bthd,bsd->bhts", q_rope, kr_win, preferred_element_type=jnp.float32)
    key_pos = jnp.arange(c_win.shape[1], dtype=jnp.int32)
    s = jnp.where((key_pos[None, None, :] <= positions[:, :, None])[:, None],
                  s * scale, jnp.float32(_NEG_INF))
    p = jax.nn.softmax(s, axis=-1).astype(c_win.dtype)
    return jnp.einsum("bhts,bsr->bthr", p, c_win)


def mla_paged_decode_eligible(t: int, r: int, itemsize: int) -> bool:
    """Whether Mosaic can tile the latent pool: block tokens on the sublane
    multiple, the latent width on the 128-lane tiling (the rotary pool's rows
    are padded to it by ``ModelConfig.kv_cache_dims``: a copy out of the pool
    cannot slice inside a lane tile)."""
    return t % (8 if itemsize >= 4 else 16) == 0 and r % 128 == 0


def _mla_kernel(tbl_ref, pos_ref, layer_ref, qt_ref, qr_ref, c_hbm, r_hbm, o_ref,
                c_buf, r_buf, acc_ref, m_ref, l_ref, sem, parity,
                *, scale: float, t: int, k: int, nb: int, group: int, w: int):
    """One grid step = one SLOT, all its query heads: ``_paged_kernel``'s walk
    (runs of k table entries into one half of a double buffer, the next run,
    or the next slot's first, started behind the one attended to; entries
    past the last live block re-read it and are masked) over the two pools of
    a latent cache. Row r of the query tiles is (query offset r // group,
    head r % group), so the causal frontier is ``key_pos <= pos + r // group``."""
    b, slots = pl.program_id(0), pl.num_programs(0)
    layer = layer_ref[0]
    rows = qt_ref.shape[-2]

    def last_block(bi):
        return jnp.minimum(jnp.maximum(pos_ref[bi] + w - 1, 0) // t, nb - 1)

    def run_copies(bi, run, half, last):
        out = []
        for i in range(k):
            blk = 0 if last is None else tbl_ref[bi, jnp.minimum(run * k + i, last)]
            for src, dst in ((c_hbm, c_buf), (r_hbm, r_buf)):
                out.append(pltpu.make_async_copy(
                    src.at[blk, layer], dst.at[half, i], sem.at[half]))
        return out

    def start(bi, run, half):
        for c in run_copies(bi, run, half, last_block(bi)):
            c.start()

    def run_tiles(buf, half):  # k x [1, T, W] -> [1, k*T, W]
        tiles = [buf[half, i] for i in range(k)]
        return tiles[0] if k == 1 else jnp.concatenate(tiles, axis=1)

    @pl.when(b == 0)
    def _first():
        parity[0] = 0
        start(b, 0, 0)

    pos = pos_ref[b]
    runs = last_block(b) // k + 1
    first = parity[0]
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    def attend(r, carry):
        half = (first + r) % 2
        more = r + 1 < runs

        @pl.when(jnp.logical_or(more, b + 1 < slots))
        def _prefetch():
            start(jnp.where(more, b, jnp.minimum(b + 1, slots - 1)),
                  jnp.where(more, r + 1, 0), 1 - half)

        for c in run_copies(b, r, half, None):
            c.wait()
        qt, qr = qt_ref[0], qr_ref[0]  # [1, rows, R], [1, rows, dr]
        cc = run_tiles(c_buf, half).astype(qt.dtype)
        rr = run_tiles(r_buf, half).astype(qr.dtype)
        dims = (((2,), (2,)), ((0,), (0,)))
        s = (jax.lax.dot_general(qt, cc, dims, preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr, rr, dims, preferred_element_type=jnp.float32)
             ) * scale  # [1, rows, k*T]
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, k * t), 0)
        key_pos = r * (k * t) + jax.lax.broadcasted_iota(jnp.int32, (rows, k * t), 1)
        s = jnp.where((key_pos <= pos + row // group)[None], s, _NEG_INF)
        m_prev = m_ref[:, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, :, :1] * corr + jnp.sum(p, axis=2, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(cc.dtype), cc, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
        return carry

    jax.lax.fori_loop(0, runs, attend, 0)
    parity[0] = (first + runs) % 2
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[:, :, :1], 1e-30)).astype(o_ref.dtype)


def mla_paged_decode_attention(
    qt: jax.Array,      # [B, W, H, R] queries in latent space, positions pos..pos+W-1
    q_rope: jax.Array,  # [B, W, H, dr]
    c_pool: jax.Array,  # [NBp, L, 1, T, R] latents
    r_pool: jax.Array,  # [NBp, L, 1, T, dr] rotary keys (dr a lane multiple on a chip)
    tbl: jax.Array,     # [B, NB] int32 block ids
    pos: jax.Array,     # [B] int32: first query position per slot
    layer,              # int32 scalar (a traced scan index is fine)
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """Absorbed attention of W new tokens a slot over the slot's whole paged
    history, read run by run from the two pools. Returns [B, W, H, R] in
    qt.dtype. The W new rows must be in the pools already (write, then
    attend), as for ``paged_decode_attention``."""
    b, w, hq, r = qt.shape
    dr = q_rope.shape[-1]
    t, nb = c_pool.shape[3], tbl.shape[1]
    # the landing buffers of a run: latent tile + rotary tile (lane-padded)
    k = _run_blocks(t, nb, 1, (r + max(dr, 128)) // 2, c_pool.dtype.itemsize)
    rows = hq * w
    mult = 8 if qt.dtype.itemsize >= 4 else 16
    rows_p = -(-rows // mult) * mult

    def fold(q):  # [B, W, H, D] -> [B, 1, rows_p, D], row = offset * H + head
        q = q.reshape(b, 1, rows, q.shape[-1])
        return q if rows_p == rows else jnp.pad(q, ((0, 0), (0, 0), (0, rows_p - rows), (0, 0)))

    def q_map(bi, tbl_ref, pos_ref, layer_ref):
        return (bi, 0, 0, 0)

    kernel = functools.partial(_mla_kernel, scale=scale, t=t, k=k, nb=nb, group=hq, w=w)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, 1, rows_p, r), q_map),
                  pl.BlockSpec((1, 1, rows_p, dr), q_map),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, 1, rows_p, r), q_map),
        scratch_shapes=[
            pltpu.VMEM((2, k, 1, t, r), c_pool.dtype),
            pltpu.VMEM((2, k, 1, t, dr), r_pool.dtype),
            pltpu.VMEM((1, rows_p, r), jnp.float32),
            pltpu.VMEM((1, rows_p, 128), jnp.float32),
            pltpu.VMEM((1, rows_p, 128), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, rows_p, r), qt.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        # a constant: the custom call's name in a device trace
        name="mla_paged_decode_attention",
    )(
        tbl.astype(jnp.int32),
        jnp.asarray(pos, jnp.int32).reshape(b),
        jnp.asarray(layer, jnp.int32).reshape(1),
        fold(qt), fold(q_rope), c_pool, r_pool,
    )
    return out[:, 0, :rows].reshape(b, w, hq, r)


def mla_paged_decode_attention_auto(qt, q_rope, c_pool, r_pool, tbl, pos, layer,
                                    scale: float) -> jax.Array:
    """The kernel, through the Pallas interpreter off-TPU (the CPU tests run
    the kernel's own code)."""
    return mla_paged_decode_attention(
        qt, q_rope, c_pool, r_pool, tbl, pos, layer, scale,
        interpret=jax.default_backend() != "tpu")
