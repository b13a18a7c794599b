"""Paged-attention decode as a Pallas TPU kernel.

vLLM-style PagedAttention for the decode path (SURVEY.md §7 hard part #2,
ROADMAP item 1): one grid cell per SLOT, every kv head of the pool inside
it, reading the slot's block table from scalar-prefetch SMEM. The cell walks
the slot's live table entries in runs of k consecutive entries: the pools
``[NB, L, Hkv, T, D]`` stay in HBM, one block id and layer give a contiguous
``[Hkv, T, D]`` slab, and the kernel copies a run's k slabs into a
double-buffered VMEM landing area itself, dequantizes int8 KVQ codes per
run, and folds one ``[rows, D] x [D, k*T]`` product per head into an online
softmax. This removes the two costs of the XLA fallback in serve/batcher.py:

- ``kv_pool_gather_view`` materializes every slot's live window as a dense
  [B, L, Hkv, W, D] copy per decode step (HBM round-trip proportional to
  context, not to the one new token);
- the pow2 window ladder re-jits ``decode_pos_paged`` per (bucket, window)
  pair as contexts grow.

The table width (static = max_seq/T) is only the bound of the walk: its trip
count is the slot's live runs, a scalar computed from ``pos``, so one
compiled program serves every context length and the kernel's time follows
the KV that is live — a slot with no request costs one run of the null
block, not a table's worth of grid steps. (The grid this replaced was one
cell per (slot, kv-head, pool-block): 8,192 cells a layer at 8 slots x 8
heads x 128 entries, ~175 ns each whatever the context; PERF.md, PR 26.)

Queries arrive as the slot's GQA group x query-width bundle: decode is
W == 1, speculative verify passes the draft bundle W == k+1 — one kernel,
one compiled program per width. Off-TPU the kernel runs in interpreter mode
(bit-level tests on the CPU backend; the interpreter performs a copy where
it is started, so only a chip run orders the double buffer);
``paged_decode_eligible`` gates the auto-downshift to the XLA path for
shapes Mosaic cannot tile.

After it: ``paged_decode_attention_picked``, the same walk over the blocks
each kv head of a block-sparse layer picked for the step (a table a slot and
kv head, one head's slab a copy), under a name of its own. At the end of the
file: ``window_decode_attention``, the decode kernel of a
window-attention layer over a slot's ring (no table, no walk), under a name
of its own.
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
    heads. int8 KVQ codes pack 32 rows to a native tile, but every copied
    K/V slab spans the pool's WHOLE [T, D] minor plane, which Mosaic accepts
    below the native tile: codes at the default KV_BLOCK_TOKENS=16 compile
    for a v5e (tests/test_tpu_compile.py), so TPU_KV_QUANT=int8 keeps the
    kernel at the default block size. Anything else downshifts to the XLA
    path."""
    sub = 8 if itemsize >= 4 and not quantized else 16
    return t % sub == 0 and d % 128 == 0 and hkv % tp == 0


# The keys one step of a slot's walk attends to: a run of k = _RUN_TOKENS // T
# consecutive table entries, two passes of the 128-wide MXU per kv head.
# Measured on a v5e at the benchmark's shapes (PERF.md, PR 26): 256 reads a
# full 2048-token table at 87 % of HBM bandwidth where 128 reads it at 71 %
# (a run's copies cost a fixed issue time), 512 is no better and re-reads more
# of the last block, and at ~400 tokens a slot all three are within 5 %.
# That is 1 MiB a run at 4,096 B a token (8 kv heads x 128, K and V, bf16). The
# latent kernel no longer takes it (``ops/mla_attention._run_entries``, PR 49:
# its rows are 1,280 B); a dense cache under 4 KiB a token still does.
_RUN_TOKENS = 256
# What the landing buffers of one run (K and V, two halves each) may take of
# VMEM: a quarter of a v5e core's 16 MiB scoped default.
_RUN_VMEM_BYTES = 4 << 20


def _run_blocks(t: int, nb: int, hkv: int, d: int, itemsize: int) -> int:
    """k: how many table entries one run covers. The largest divisor of the
    table width ``nb`` whose keys fit ``_RUN_TOKENS`` and whose landing
    buffers ([Hkv, T, D] per entry, K and V, two halves) fit
    ``_RUN_VMEM_BYTES`` — all from shapes, so a model with more kv heads or
    a wider block gets a shorter run, never a knob."""
    per_entry = 2 * 2 * hkv * t * d * itemsize
    cap = max(1, min(_RUN_TOKENS // t, _RUN_VMEM_BYTES // per_entry, nb))
    return next(k for k in range(cap, 0, -1) if nb % k == 0)


def _paged_kernel(
    tbl_ref, pos_ref, layer_ref, q_ref, *refs,
    scale: float, t: int, k: int, nb: int, group: int, w: int, quantized: bool
):
    """One grid step = one SLOT, every kv head of the (local) pool inside it;
    the step walks the slot's live table entries in RUNS of k and stops at
    the last live one, so a slot costs what its context holds, not the table
    width. The K and V pools (the int8 codes of a KVQ pool) stay in HBM
    (``refs`` = the two pools, a KVQ pool's two scale views, the output, a
    VMEM landing buffer per pool, the softmax scratch, DMA semaphores, a
    parity word): a run is k async copies per pool of the [Hkv, T, D] slab
    that one block id and layer give into one half of a double buffer,
    started while the run before it is attended to. The first run of the
    NEXT slot is started behind this slot's last one, so only slot 0 waits
    for a cold copy; the parity word carries which half that run landed in
    across grid steps (the grid runs in order). An entry past the last live
    block re-reads that block and is masked by key_pos, so every tile of a
    live run holds real rows. A KVQ pool's f32 scales arrive as the slot's
    rows already gathered by the caller, one [Hkv, k*T] tile per run
    (``paged_decode_attention`` says why), and the codes are dequantised
    per run, in VMEM.

    q rows are the slot's GQA bundle per kv head (row r = query-offset
    r//group within the W-wide bundle, q-head r%group within the group), so
    the causal frontier is per-row: ``key_pos <= pos + r//group``. Rows
    written this step (write-then-attend in models/llama.py) are already in
    the pool, so the frontier includes them. The k tiles are joined into one
    [Hkv, k*T, D] operand, so a head's scores are ONE [rows, D] x [D, k*T]
    product, folded into the online-softmax scratch. Slots whose table is
    unallocated read the null block (id 0) and produce finite junk the
    caller discards — the same contract as the XLA gather-view path."""
    hbm, refs = refs[:2], refs[2:]
    scales, refs = (refs[:2], refs[2:]) if quantized else ((None, None), refs)
    o_ref, *bufs, acc_ref, m_ref, l_ref, sem, parity = refs
    b, slots = pl.program_id(0), pl.num_programs(0)
    layer = layer_ref[0]
    rows = q_ref.shape[-2]

    def last_block(bi):
        return jnp.minimum(jnp.maximum(pos_ref[bi] + w - 1, 0) // t, nb - 1)

    def run_copies(bi, run, half, last):
        """The async copies of run ``run`` of slot ``bi`` into ``half``;
        ``last`` None builds them for a wait, which needs the shapes and the
        semaphore only."""
        out = []
        for i in range(k):
            blk = 0 if last is None else tbl_ref[bi, jnp.minimum(run * k + i, last)]
            for src, dst in zip(hbm, bufs):
                out.append(pltpu.make_async_copy(
                    src.at[blk, layer], dst.at[half, i], sem.at[half]))
        return out

    def start(bi, run, half):
        for c in run_copies(bi, run, half, last_block(bi)):
            c.start()

    def run_tiles(buf, srows, half, r, dtype):
        # dequant in f32, cast after: Mosaic's minor-dim [T] -> [T, 1]
        # insertion only lowers for 32-bit vectors (ops/flash_attention.py)
        mid = jnp.float32 if quantized else dtype
        tiles = [buf[half, i].astype(mid) for i in range(k)]  # k x [Hkv, T, D]
        x = tiles[0] if k == 1 else jnp.concatenate(tiles, axis=1)
        if quantized:
            x = x * srows[0, r].astype(jnp.float32)[:, :, None]
        return x.astype(dtype)  # [Hkv, k*T, D]

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
        q = q_ref[0]  # [Hkv, rows, D]
        kk = run_tiles(bufs[0], scales[0], half, r, q.dtype)
        vv = run_tiles(bufs[1], scales[1], half, r, q.dtype)
        s = jax.lax.dot_general(
            q, kk, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
        ) * scale  # [Hkv, rows, k*T] f32
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, k * t), 0)
        key_pos = r * (k * t) + jax.lax.broadcasted_iota(jnp.int32, (rows, k * t), 1)
        s = jnp.where((key_pos <= pos + row // group)[None], s, _NEG_INF)
        m_prev = m_ref[:, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, :, :1] * corr + jnp.sum(p, axis=2, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(vv.dtype), vv,
            (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
        return carry

    jax.lax.fori_loop(0, runs, attend, 0)
    parity[0] = (first + runs) % 2
    out = acc_ref[...] / jnp.maximum(l_ref[:, :, :1], 1e-30)
    o_ref[0] = out.astype(o_ref.dtype)


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
    history, read run-by-run straight from the pool. Returns [B, W, Hq, D]
    in q.dtype. The caller must have scattered the W new K/V rows into the
    pool first (write-then-attend); the kernel's causal mask then covers
    them exactly.

    The grid is (slots,) and a slot's walk over its table is a loop inside
    the cell whose trip count is the slot's live runs — a scalar read from
    ``pos``, not a shape — so ONE compiled program serves every context
    length and its time follows the KV that is live. A slot's blocks are
    scattered in the pool, so the pools are left in HBM and the kernel
    copies each run's k [Hkv, T, D] slabs itself (``_paged_kernel``). k
    comes from the shapes (``_run_blocks``). Per-run work per head is
    [rows, k*T] x [k*T, D]; rows = GQA group x W (padded to the sublane
    multiple).

    A KVQ pool's scale rows are [Hkv, T] f32 per block and layer, which an
    in-kernel copy cannot address (Mosaic wants the minor dim of a copied
    window on the 128-lane tiling, and T is 16 or 32), so they are gathered
    here, by table, into [B, NB/k, Hkv, k*T] — 1/D of the codes' bytes over
    the whole table — and reach the kernel as one block per slot."""
    b, w, hq, d = q.shape
    quantized = is_quantized(k_pool)
    kq = k_pool.q if quantized else k_pool
    hkv, t = kq.shape[2], kq.shape[3]
    group = hq // hkv
    nb = tbl.shape[1]
    k = _run_blocks(t, nb, hkv, d, kq.dtype.itemsize)
    rows = group * w
    mult = 8 if q.dtype.itemsize >= 4 else 16
    rows_p = -(-rows // mult) * mult

    # [B, Hkv, group*W, D]: row r = (query offset r//group, group lane
    # r%group) — head-major GQA fold, query offset outermost per group
    qh = q.reshape(b, w, hkv, group, d).transpose(0, 2, 1, 3, 4)
    qh = qh.reshape(b, hkv, rows, d)
    if rows_p != rows:
        qh = jnp.pad(qh, ((0, 0), (0, 0), (0, rows_p - rows), (0, 0)))

    def q_map(bi, tbl_ref, pos_ref, layer_ref):
        return (bi, 0, 0, 0)

    def run_scales(s):  # [NBp, L, Hkv, T] -> the slots' rows, a tile per run
        rows_of = s[tbl, layer].reshape(b, nb // k, k, hkv, t)
        return rows_of.transpose(0, 1, 3, 2, 4).reshape(b, nb // k, hkv, k * t)

    pools = (kq, v_pool.q) if quantized else (k_pool, v_pool)
    scales = (run_scales(k_pool.s), run_scales(v_pool.s)) if quantized else ()
    # a landing buffer per pool: two halves of k [Hkv, T, D] tiles
    landing = [pltpu.VMEM((2, k, hkv, t, d), p.dtype) for p in pools]

    kernel = functools.partial(
        _paged_kernel, scale=scale, t=t, k=k, nb=nb, group=group, w=w,
        quantized=quantized,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, hkv, rows_p, d), q_map)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * 2
        + [pl.BlockSpec((1, nb // k, hkv, k * t), q_map)] * len(scales),
        out_specs=pl.BlockSpec((1, hkv, rows_p, d), q_map),
        scratch_shapes=landing + [
            pltpu.VMEM((hkv, rows_p, d), jnp.float32),
            pltpu.VMEM((hkv, rows_p, 128), jnp.float32),
            pltpu.VMEM((hkv, rows_p, 128), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows_p, d), q.dtype),
        # slot b+1's first run is started by slot b: the grid runs in order
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        # a constant: it is the custom call's name in a device trace and part
        # of the program's bytes, so of its compile-cache key
        name="paged_decode_attention",
    )(
        tbl.astype(jnp.int32),
        jnp.asarray(pos, jnp.int32).reshape(b),
        jnp.asarray(layer, jnp.int32).reshape(1),
        qh, *pools, *scales,
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


# ---------------------------------------------------------------------------
# block-sparse layers: decode over the blocks a kv head picked
# ---------------------------------------------------------------------------
#
# A block-sparse layer (models/sala.py) attends, past its dense length, over
# the blocks of keys each KV HEAD picked for the step: a table a (slot, kv
# head) of pool block ids, of which the first ``count`` are walked. Every
# entry but the last is a full block; the last (the frontier block, which the
# selection always takes) holds ``last_len`` keys. The keys carry no rotary
# position, so where a block stands in the walk does not matter: the walk of
# ``_paged_kernel`` with a head axis on the table, one head's [T, D] slab a
# copy, and a mask that counts keys from the table's start.

# the keys one run of a head's walk attends to: 16 KiB a block and pool at
# T = 64 (bf16), so a run of 256 would be four small copies a pool
_PICKED_RUN_TOKENS = 512


def _picked_kernel(
    ent_ref, cnt_ref, len_ref, layer_ref, q_ref, k_hbm, v_hbm, o_ref,
    kbuf, vbuf, acc_ref, m_ref, l_ref, sem, parity,
    *, scale: float, t: int, k: int, hkv: int
):
    """One grid step = one (slot, kv head): cell c is head ``c % hkv`` of slot
    ``c // hkv``. It walks the first ``cnt[c]`` entries of its row of the
    picked table in runs of k, double-buffered as ``_paged_kernel``'s walk is
    (the first run of cell c + 1 is started behind this cell's last one; the
    parity word says which half it landed in). An entry past the count
    re-reads the last one and is masked: key i of the walk is real iff
    i < (cnt - 1) t + last_len."""
    c, cells = pl.program_id(0), pl.num_programs(0)
    layer = layer_ref[0]
    rows = q_ref.shape[-2]

    def run_copies(ci, run, half, real: bool):
        """The async copies of run ``run`` of cell ``ci`` into ``half``; not
        ``real`` builds them for a wait, which needs the shapes and the
        semaphore only."""
        out = []
        for i in range(k):
            blk = ent_ref[ci, jnp.minimum(run * k + i, cnt_ref[ci] - 1)] if real else 0
            head = ci % hkv if real else 0
            for src, dst in ((k_hbm, kbuf), (v_hbm, vbuf)):
                out.append(pltpu.make_async_copy(
                    src.at[blk, layer, head], dst.at[half, i], sem.at[half]))
        return out

    def start(ci, run, half):
        for cp in run_copies(ci, run, half, True):
            cp.start()

    @pl.when(c == 0)
    def _first():
        parity[0] = 0
        start(c, 0, 0)

    cnt = cnt_ref[c]
    live = (cnt - 1) * t + len_ref[c]
    runs = (cnt + k - 1) // k
    first = parity[0]
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    def attend(r, carry):
        half = (first + r) % 2
        more = r + 1 < runs

        @pl.when(jnp.logical_or(more, c + 1 < cells))
        def _prefetch():
            start(jnp.where(more, c, jnp.minimum(c + 1, cells - 1)),
                  jnp.where(more, r + 1, 0), 1 - half)

        for cp in run_copies(c, r, half, False):
            cp.wait()
        q = q_ref[0]  # [rows, D]
        kk = kbuf[half].reshape(k * t, kbuf.shape[-1]).astype(q.dtype)
        vv = vbuf[half].reshape(k * t, vbuf.shape[-1]).astype(q.dtype)
        s = jax.lax.dot_general(
            q, kk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [rows, k*T] f32
        at = r * (k * t) + jax.lax.broadcasted_iota(jnp.int32, (rows, k * t), 1)
        s = jnp.where(at < live, s, _NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(vv.dtype), vv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
        return carry

    jax.lax.fori_loop(0, runs, attend, 0)
    parity[0] = (first + runs) % 2
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)).astype(o_ref.dtype)


def paged_decode_attention_picked(
    q: jax.Array,        # [B, 1, Hq, D]: the query of each slot
    k_pool: jax.Array,   # [NBp, L, Hkv, T, D]
    v_pool: jax.Array,
    entries: jax.Array,  # [B, Hkv, P] int32 pool block ids, a kv head's picks
    count: jax.Array,    # [B, Hkv] int32: entries to walk, >= 1
    last_len: jax.Array,  # [B] int32: keys the LAST walked entry holds, 1..T
    layer,
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """Attention of one new token a slot over the pool blocks each kv head
    picked: head h of slot b reads ``entries[b, h, :count[b, h]]``, every
    block whole but the last. One compiled program for every count; its time
    follows the picked keys. Returns [B, 1, Hq, D] in q.dtype. (The order of
    the entries is the caller's: softmax attention over keys without a rotary
    position does not care.)"""
    b, w, hq, d = q.shape
    if w != 1:
        raise NotImplementedError("the picked walk decodes one position a slot")
    hkv, t = k_pool.shape[2], k_pool.shape[3]
    group, width = hq // hkv, entries.shape[-1]
    k = max(1, min(_PICKED_RUN_TOKENS // t, width))
    mult = 8 if q.dtype.itemsize >= 4 else 16
    rows_p = -(-group // mult) * mult
    qh = q.reshape(b * hkv, group, d)
    if rows_p != group:
        qh = jnp.pad(qh, ((0, 0), (0, rows_p - group), (0, 0)))

    def q_map(c, *_):
        return (c, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b * hkv,),
        in_specs=[pl.BlockSpec((1, rows_p, d), q_map)] + [pl.BlockSpec(memory_space=pl.ANY)] * 2,
        out_specs=pl.BlockSpec((1, rows_p, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((2, k, t, d), k_pool.dtype),
            pltpu.VMEM((2, k, t, d), v_pool.dtype),
            pltpu.VMEM((rows_p, d), jnp.float32),
            pltpu.VMEM((rows_p, 128), jnp.float32),
            pltpu.VMEM((rows_p, 128), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_picked_kernel, scale=scale, t=t, k=k, hkv=hkv),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hkv, rows_p, d), q.dtype),
        # cell c+1's first run is started by cell c: the grid runs in order
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        # its own constant name: a device trace tells the picked walk from the
        # whole-table walk (paged_decode_attention) by it
        name="paged_decode_attention_picked",
    )(
        entries.astype(jnp.int32).reshape(b * hkv, width),
        jnp.maximum(count.astype(jnp.int32), 1).reshape(b * hkv),
        jnp.repeat(jnp.asarray(last_len, jnp.int32).reshape(b), hkv),
        jnp.asarray(layer, jnp.int32).reshape(1),
        qh, k_pool, v_pool,
    )
    return out[:, :group].reshape(b, 1, hq, d)


def paged_decode_attention_picked_auto(q, k_pool, v_pool, entries, count, last_len, layer,
                                       scale: float) -> jax.Array:
    interpret = jax.default_backend() != "tpu"
    return paged_decode_attention_picked(q, k_pool, v_pool, entries, count, last_len, layer,
                                         scale, interpret=interpret)


def paged_decode_attention_picked_xla(q, k_pool, v_pool, entries, count, last_len, layer,
                                      scale: float) -> jax.Array:
    """The picked walk in plain XLA (what the kernel is held to in the tests;
    no program runs it): every entry gathered, the keys past the walk masked."""
    b, _, hq, d = q.shape
    hkv, t = k_pool.shape[2], k_pool.shape[3]
    width = entries.shape[-1]
    heads = jnp.arange(hkv)[None, :, None]
    ks = k_pool[entries, layer, heads].reshape(b, hkv, width * t, d)  # [B, Hkv, P*T, D]
    vs = v_pool[entries, layer, heads].reshape(b, hkv, width * t, d)
    live = (jnp.maximum(count, 1) - 1) * t + last_len[:, None]        # [B, Hkv]
    qg = q.reshape(b, hkv, hq // hkv, d)
    s = jnp.einsum("bhgd,bhkd->bhgk", qg, ks.astype(q.dtype),
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where((jnp.arange(width * t) < live[..., None])[:, :, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bhkd->bhgd", p.astype(q.dtype), vs.astype(q.dtype))
    return o.reshape(b, 1, hq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# window layers: decode over a slot's ring
# ---------------------------------------------------------------------------
#
# A window-attention layer (models/swa_moe.py) sees the last R = ``window``
# keys, its own among them. They live in a ring a slot, [Lw, slots, Hkv, R, D]
# beside the pool: the key of position p at place p mod R, so a slot's keys
# of a layer are ONE contiguous [Hkv, R, D] slab that no table describes, and
# the key at place i belongs to position pos - ((pos - i) mod R): known from
# ``pos`` alone, and in the window by construction (the write of position p
# overwrites p - R). Only a position below 0 is masked.


def window_decode_eligible(r: int, d: int, itemsize: int) -> bool:
    """Whether the ring kernel can serve this layout on a real TPU: the ring
    is the sublane dim of the K/V tile, the head the lane dim."""
    return r % (8 if itemsize >= 4 else 16) == 0 and d % 128 == 0


def _window_kernel(pos_ref, layer_ref, q_ref, k_ref, v_ref, o_ref, *, scale: float, r: int):
    """One grid step = one SLOT, every kv head inside it: the slot's whole
    ring of the layer arrives as one block (the pipeline fetches slot b+1's
    while slot b is attended to), a head's scores are ONE [rows, D] x [D, R]
    product and the softmax is taken at once. A slot that holds no request
    reads its ring as it lies and gives finite junk the caller discards."""
    del layer_ref  # the ring's block index reads it
    pos = pos_ref[pl.program_id(0)]
    q = q_ref[0]  # [Hkv, rows, D]
    rows = q.shape[1]
    s = jax.lax.dot_general(
        q, k_ref[0, 0], (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
    ) * scale  # [Hkv, rows, R] f32
    # place i holds the position ``age`` behind pos: age = (pos - i) mod R,
    # without a vector remainder (the place of pos itself is a scalar)
    at = jax.lax.rem(pos, r)
    place = jax.lax.broadcasted_iota(jnp.int32, (rows, r), 1)
    age = jnp.where(place <= at, at - place, at - place + r)
    s = jnp.where((age <= pos)[None], s, _NEG_INF)
    m = jnp.max(s, axis=2, keepdims=True)
    p = jnp.exp(s - m)
    out = jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0, 0], (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ) / jnp.sum(p, axis=2, keepdims=True)
    o_ref[0] = out.astype(o_ref.dtype)


def window_decode_attention(
    q: jax.Array,       # [B, 1, Hq, D]: the query at position pos
    k_ring: jax.Array,  # [Lw, B, Hkv, R, D]: row b is slot b's ring
    v_ring: jax.Array,
    pos: jax.Array,     # [B] int32
    layer,              # int32 scalar: the layer's place among the window layers
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """Attention of one new token a slot over the slot's ring of the last R
    keys (the caller has written the token's own key at place pos mod R:
    write-then-attend, as on the pool). One compiled program for every
    context length; its time is the rings', whatever the contexts.
    Returns [B, 1, Hq, D] in q.dtype."""
    b, w, hq, d = q.shape
    if w != 1:
        raise NotImplementedError("the ring kernel decodes one position a slot")
    hkv, r = k_ring.shape[2], k_ring.shape[3]
    group = hq // hkv
    mult = 8 if q.dtype.itemsize >= 4 else 16
    rows_p = -(-group // mult) * mult
    qh = q.reshape(b, hkv, group, d)
    if rows_p != group:
        qh = jnp.pad(qh, ((0, 0), (0, 0), (0, rows_p - group), (0, 0)))

    def q_map(bi, pos_ref, layer_ref):
        return (bi, 0, 0, 0)

    def ring_map(bi, pos_ref, layer_ref):
        return (layer_ref[0], bi, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, hkv, rows_p, d), q_map),
                  pl.BlockSpec((1, 1, hkv, r, d), ring_map),
                  pl.BlockSpec((1, 1, hkv, r, d), ring_map)],
        out_specs=pl.BlockSpec((1, hkv, rows_p, d), q_map),
    )
    out = pl.pallas_call(
        functools.partial(_window_kernel, scale=scale, r=r),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows_p, d), q.dtype),
        interpret=interpret,
        # its own constant name: a device trace tells the window layers'
        # calls from the full layers' (paged_decode_attention) by it
        name="window_decode_attention",
    )(
        jnp.asarray(pos, jnp.int32).reshape(b),
        jnp.asarray(layer, jnp.int32).reshape(1),
        qh, k_ring, v_ring,
    )
    return out[:, :, :group].reshape(b, 1, hq, d)


def window_decode_attention_auto(q, k_ring, v_ring, pos, layer, scale: float) -> jax.Array:
    interpret = jax.default_backend() != "tpu"
    return window_decode_attention(q, k_ring, v_ring, pos, layer, scale, interpret=interpret)
