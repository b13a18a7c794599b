"""Absorbed latent-attention (MLA) decode: the XLA form and a Pallas TPU
kernel over the paged pool.

In the absorbed form a head's query lives in latent space
(``q~_h = W_uk_h q_nope_h``, ``models/mla_moe.py``), so every head scores the
SAME cached rows: the normalised latent ``c`` (kv_lora_rank wide) and the one
rotated key ``k_r`` all heads share. The values are the latents again:

    score_h[s] = (q~_h . c[s] + q_rope_h . k_r[s]) * scale
    o_lat_h    = sum_s softmax(score_h)[s] c[s]          (W_uv applied by the caller)

That is multi-query attention with one "kv head" of two unlike widths whose
V is its own K's first part. ``mla_paged_decode_attention`` walks block tables
as ``ops/paged_attention.py`` does (PR 26's kernel: runs of k consecutive
table entries copied into a double buffer, the trip count the slot's live
runs), with every query head of a slot in one grid step: a run is read from
HBM once for all 32 heads. What the walk costs is the live contexts' bytes:
the run is sized by this cache's own row (``_run_entries``), the grid visits
the slots that hold a request and no other (the launch's ``LiveSlots``), and
a slot's last run copies its live blocks and nothing behind them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import _NEG_INF, _RUN_VMEM_BYTES
from .ssm_scan import LiveSlots


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


# The bytes one run of a slot's walk copies out of a latent cache. A latent
# token is R + the lane-padded rotary width: 1,280 B at 512 + 128 in bf16, under
# a third of the dense cache's 4,096 B for which ``ops/paged_attention``'s
# ``_RUN_TOKENS`` was measured. Measured on a v5e at the two cells' shapes
# (PERF.md §5, PR 49): a run's copies and its pass of the products cost a fixed
# time each, so this walk reads ``kanana2``'s live rows (4 x ~13.8k tokens) at
# 50 % of HBM bandwidth in runs of 320 KiB, 70 % at 640 KiB, 85 % at 1,280 KiB
# and no better at 2,560 KiB; ``xing29b``'s (7 x 0.6-3.5k in blocks of 16) are
# read fastest at 1,280 KiB too.
_RUN_BYTES = 1280 << 10
# The copies of a run are started, and waited for, this many table entries a
# loop turn. One entry a turn costs 9-16 % of a call (the scalar core issues
# nothing beside the loop's own bookkeeping); a whole run unrolled (64 entries
# of 16 tokens, twice two copies, at three places) is no faster than eight a
# turn (101.2 against 102.5 us a call at ``kanana2``'s shapes, 58.8 against
# 58.1 at ``xing29b``'s) and is traced in Python for every decode program:
# +11 s of ``xing29b``'s 78 s set-up (PERF.md §6, PR 49).
_UNROLL = 8


def _run_entries(t: int, nb: int, row_bytes: int) -> int:
    """k: the table entries one run copies, from shapes alone: as many blocks
    of ``t`` cache rows as ``_RUN_BYTES``, the landing buffers' share of VMEM
    (two halves in the dense kernel's ``_RUN_VMEM_BYTES``) and the table's
    width allow."""
    block = t * row_bytes
    return max(1, min(_RUN_BYTES // block, _RUN_VMEM_BYTES // (2 * block), nb))


def _mla_kernel(tbl_ref, pos_ref, layer_ref, order_ref, n_ref, qt_ref, qr_ref, c_hbm, r_hbm,
                o_ref, c_buf, r_buf, acc_ref, m_ref, l_ref, sem, parity,
                *, scale: float, t: int, k: int, nb: int, group: int, w: int):
    """Grid place g = the SLOT ``order[g]``, all its query heads; a place past
    ``n`` does nothing (its blocks are the ones the place before it named). A
    slot's walk copies its LIVE table entries, and no other, in runs of k into
    one half of a double buffer ([k T, width] a pool: entry i lands at rows
    i T), the next run, or the first run of the next slot on the list,
    started behind the one attended to. The rows of a half past the last live
    block hold what an earlier run left there, or the zeros the buffers start
    a call with: finite, and masked by key_pos. Row r of the query tiles is
    (query offset r // group, head r % group), so the causal frontier is
    ``key_pos <= pos + r // group``."""
    g, places, n = pl.program_id(0), pl.num_programs(0), n_ref[0]
    layer = layer_ref[0]
    rows = qt_ref.shape[-2]

    def live_blocks(slot):
        return jnp.minimum(jnp.maximum(pos_ref[slot] + w - 1, 0) // t, nb - 1) + 1

    def run_entries(slot, run, half, start: bool):
        """Start, or wait for, the copies of the live entries of ``run``:
        ``_UNROLL`` entries a loop turn, then the rest one by one."""
        count = jnp.minimum(live_blocks(slot) - run * k, k)

        def entry(i):
            # a wait needs the shapes and the semaphore only
            blk = tbl_ref[slot, run * k + i] if start else 0
            rows_at = pl.ds(pl.multiple_of(i * t, t), t)
            for src, dst in ((c_hbm, c_buf), (r_hbm, r_buf)):
                copy = pltpu.make_async_copy(
                    src.at[blk, layer, 0], dst.at[half, rows_at], sem.at[half])
                copy.start() if start else copy.wait()

        def turn(j, carry):
            for u in range(_UNROLL):
                entry(j * _UNROLL + u)
            return carry

        def one(i, carry):
            entry(i)
            return carry

        turns = count // _UNROLL
        jax.lax.fori_loop(0, turns, turn, 0)
        jax.lax.fori_loop(turns * _UNROLL, count, one, 0)

    @pl.when(jnp.logical_and(g == 0, n > 0))
    def _first():
        c_buf[...] = jnp.zeros_like(c_buf)
        r_buf[...] = jnp.zeros_like(r_buf)
        parity[0] = 0
        run_entries(order_ref[0], 0, 0, True)

    @pl.when(g < n)
    def _slot():
        slot = order_ref[g]
        pos = pos_ref[slot]
        runs = (live_blocks(slot) + k - 1) // k
        first = parity[0]
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

        def attend(r, carry):
            half = (first + r) % 2
            more = r + 1 < runs

            @pl.when(jnp.logical_or(more, g + 1 < n))
            def _prefetch():
                nxt = order_ref[jnp.minimum(g + 1, places - 1)]
                run_entries(jnp.where(more, slot, nxt), jnp.where(more, r + 1, 0),
                            1 - half, True)

            run_entries(slot, r, half, False)
            qt, qr = qt_ref[0, 0], qr_ref[0, 0]  # [rows, R], [rows, dr]
            cc = c_buf[half].astype(qt.dtype)    # [k T, R]
            rr = r_buf[half].astype(qr.dtype)
            nt = (((1,), (1,)), ((), ()))
            s = (jax.lax.dot_general(qt, cc, nt, preferred_element_type=jnp.float32)
                 + jax.lax.dot_general(qr, rr, nt, preferred_element_type=jnp.float32)
                 ) * scale  # [rows, k T]
            row = jax.lax.broadcasted_iota(jnp.int32, (rows, k * t), 0)
            key_pos = r * (k * t) + jax.lax.broadcasted_iota(jnp.int32, (rows, k * t), 1)
            s = jnp.where(key_pos <= pos + row // group, s, _NEG_INF)
            m_prev = m_ref[:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_ref[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[...] = acc_ref[...] * corr + jnp.dot(
                p.astype(cc.dtype), cc, preferred_element_type=jnp.float32)
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
            l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
            return carry

        jax.lax.fori_loop(0, runs, attend, 0)
        parity[0] = (first + runs) % 2
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)).astype(o_ref.dtype)


def mla_paged_decode_attention(
    qt: jax.Array,      # [B, W, H, R] queries in latent space, positions pos..pos+W-1
    q_rope: jax.Array,  # [B, W, H, dr]
    c_pool: jax.Array,  # [NBp, L, 1, T, R] latents
    r_pool: jax.Array,  # [NBp, L, 1, T, dr] rotary keys (dr a lane multiple on a chip)
    tbl: jax.Array,     # [B, NB] int32 block ids
    pos: jax.Array,     # [B] int32: first query position per slot
    live: LiveSlots,    # the slots that hold a request: ``ops/ssm_scan.live_slots``
    layer,              # int32 scalar (a traced scan index is fine)
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """Absorbed attention of W new tokens a slot over the slot's whole paged
    history, read run by run from the two pools: the ``live`` slots' live
    blocks once each, and nothing of a slot that holds no request, whose rows
    come back as zeros. Returns [B, W, H, R] in qt.dtype. The W new rows must
    be in the pools already (write, then attend), as for
    ``paged_decode_attention``."""
    b, w, hq, r = qt.shape
    dr = q_rope.shape[-1]
    t, nb = c_pool.shape[3], tbl.shape[1]
    k = _run_entries(t, nb, (r + dr) * c_pool.dtype.itemsize)
    rows = hq * w
    mult = 8 if qt.dtype.itemsize >= 4 else 16
    rows_p = -(-rows // mult) * mult

    def fold(q):  # [B, W, H, D] -> [B, 1, rows_p, D], row = offset * H + head
        q = q.reshape(b, 1, rows, q.shape[-1])
        return q if rows_p == rows else jnp.pad(q, ((0, 0), (0, 0), (0, rows_p - rows), (0, 0)))

    def q_map(g, tbl_ref, pos_ref, layer_ref, order_ref, n_ref):
        return (order_ref[g], 0, 0, 0)

    kernel = functools.partial(_mla_kernel, scale=scale, t=t, k=k, nb=nb, group=hq, w=w)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, 1, rows_p, r), q_map),
                  pl.BlockSpec((1, 1, rows_p, dr), q_map),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, 1, rows_p, r), q_map),
        scratch_shapes=[
            pltpu.VMEM((2, k * t, r), c_pool.dtype),
            pltpu.VMEM((2, k * t, dr), r_pool.dtype),
            pltpu.VMEM((rows_p, r), jnp.float32),
            pltpu.VMEM((rows_p, 128), jnp.float32),
            pltpu.VMEM((rows_p, 128), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, rows_p, r), qt.dtype),
        # a place starts the first run of the next: the grid runs in order
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        # a constant: the custom call's name in a device trace
        name="mla_paged_decode_attention",
    )(
        tbl.astype(jnp.int32),
        jnp.asarray(pos, jnp.int32).reshape(b),
        jnp.asarray(layer, jnp.int32).reshape(1),
        live.order,
        jnp.asarray(live.n, jnp.int32).reshape(1),
        fold(qt), fold(q_rope), c_pool, r_pool,
    )
    # the rows no place named hold whatever the buffer held
    out = jnp.where(live.mask[:, None, None, None], out, jnp.zeros((), out.dtype))
    return out[:, 0, :rows].reshape(b, w, hq, r)


def mla_paged_decode_attention_auto(qt, q_rope, c_pool, r_pool, tbl, pos, live, layer,
                                    scale: float) -> jax.Array:
    """The kernel, through the Pallas interpreter off-TPU (the CPU tests run
    the kernel's own code)."""
    return mla_paged_decode_attention(
        qt, q_rope, c_pool, r_pool, tbl, pos, live, layer, scale,
        interpret=jax.default_backend() != "tpu")
