"""The latent decode kernel alone on the chip, at the two cells' shapes: the
device time of one call (from a profiler trace: the custom call's own events)
by run length, by how many slots hold no request and by context; the kernel
against the XLA form over the gathered view; and PR 29's walk (a copy kept
here: every slot visited, runs that re-read the last live block) whole, with
its copies taken out and with its products taken out. The package's kernel
takes no switch: the run length is set by replacing ``_run_entries`` here.

Run:  python scripts/sweep_mla_kernel.py [parts]   parts: any of check,rule,run,ctx,split

Prints one line a reading and a least-squares fit t = a runs + c slots + d MiB
a cell; writes all of it to chiprun_out/mla_kernel_sweep.json.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import tempfile

sys.path.insert(0, ".")
sys.path.insert(0, "benchmark")

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lib.reduce_trace import find_xplane, is_device_plane, load_planes
from nats_llm_studio_tpu.ops import mla_attention as M
from nats_llm_studio_tpu.ops.ssm_scan import live_slots

CELLS = {
    # slots, block tokens, table width, layers, live contexts
    "kanana2": dict(slots=16, t=64, nb=512, layers=6,
                    ctx=[12800, 13500, 14100, 14700]),
    "xing29b": dict(slots=8, t=16, nb=256, layers=7,
                    ctx=[620, 1100, 1500, 1900, 2400, 2900, 3500]),
}
HQ, R, DR = 32, 512, 128
RULE = M._run_entries  # the package's own rule, which a reading replaces
STEPS = 4           # x layers calls in one launch


def _old_kernel(tbl_ref, pos_ref, layer_ref, qt_ref, qr_ref, c_hbm, r_hbm, o_ref,
                c_buf, r_buf, acc_ref, m_ref, l_ref, sem, parity,
                *, scale, t, k, nb, group, w, dma, compute, mask):
    """PR 29's ``_mla_kernel`` with three switches: without ``dma`` no copy is
    started or waited for, without ``compute`` a run is waited for and one row
    of it added up, without ``mask`` the causal select is left out."""
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
        if dma:
            for c in run_copies(bi, run, half, last_block(bi)):
                c.start()

    def run_tiles(buf, half):
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
    m_ref[...] = jnp.full_like(m_ref, M._NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    def attend(r, carry):
        half = (first + r) % 2
        more = r + 1 < runs

        @pl.when(jnp.logical_or(more, b + 1 < slots))
        def _prefetch():
            start(jnp.where(more, b, jnp.minimum(b + 1, slots - 1)),
                  jnp.where(more, r + 1, 0), 1 - half)

        if dma:
            for c in run_copies(b, r, half, None):
                c.wait()
        if not compute:
            acc_ref[0, :16, :] += c_buf[half, 0, 0, :16, :].astype(jnp.float32)
            return carry
        qt, qr = qt_ref[0], qr_ref[0]
        cc = run_tiles(c_buf, half).astype(qt.dtype)
        rr = run_tiles(r_buf, half).astype(qr.dtype)
        dims = (((2,), (2,)), ((0,), (0,)))
        s = (jax.lax.dot_general(qt, cc, dims, preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr, rr, dims, preferred_element_type=jnp.float32)
             ) * scale
        if mask:
            row = jax.lax.broadcasted_iota(jnp.int32, (rows, k * t), 0)
            key_pos = r * (k * t) + jax.lax.broadcasted_iota(jnp.int32, (rows, k * t), 1)
            s = jnp.where((key_pos <= pos + row // group)[None], s, M._NEG_INF)
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


def old_call(qt, qr, c_pool, r_pool, tbl, pos, live, layer, scale, *, k, dma, compute, mask):
    del live  # PR 29's walk visits every slot
    b, w, hq, r = qt.shape
    dr = qr.shape[-1]
    t, nb = c_pool.shape[3], tbl.shape[1]
    rows_p = hq * w
    q_map = lambda bi, *_: (bi, 0, 0, 0)  # noqa: E731
    kernel = functools.partial(_old_kernel, scale=scale, t=t, k=k, nb=nb, group=hq, w=w,
                               dma=dma, compute=compute, mask=mask)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(b,),
        in_specs=[pl.BlockSpec((1, 1, rows_p, r), q_map),
                  pl.BlockSpec((1, 1, rows_p, dr), q_map),
                  pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, 1, rows_p, r), q_map),
        scratch_shapes=[pltpu.VMEM((2, k, 1, t, r), c_pool.dtype),
                        pltpu.VMEM((2, k, 1, t, dr), r_pool.dtype),
                        pltpu.VMEM((1, rows_p, r), jnp.float32),
                        pltpu.VMEM((1, rows_p, 128), jnp.float32),
                        pltpu.VMEM((1, rows_p, 128), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,)), pltpu.SMEM((1,), jnp.int32)])
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, rows_p, r), qt.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        name="mla_sweep_split",
    )(tbl, pos, jnp.asarray(layer, jnp.int32).reshape(1),
      qt.reshape(b, 1, rows_p, r), qr.reshape(b, 1, rows_p, dr), c_pool, r_pool)
    return out.reshape(b, w, hq, r)


def inputs(cell, slots, ctx, seed=0, w=1):
    """Pools that hold the live rows' blocks scattered, a table per slot (live
    rows first in every second slot, so that empty slots lie between them)."""
    c = CELLS[cell]
    t, nb, layers = c["t"], c["nb"], c["layers"]
    rng = np.random.default_rng(seed)
    need = sum(-(-(x + w) // t) for x in ctx)
    blocks = 1 + need + 64
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    c_pool = jax.random.normal(ks[0], (blocks, layers, 1, t, R), jnp.bfloat16)
    r_pool = jax.random.normal(ks[1], (blocks, layers, 1, t, DR), jnp.bfloat16)
    qt = jax.random.normal(ks[2], (slots, w, HQ, R), jnp.bfloat16)
    qr = jax.random.normal(ks[3], (slots, w, HQ, DR), jnp.bfloat16)
    ids = rng.permutation(np.arange(1, 1 + need))
    tbl = np.zeros((slots, nb), np.int32)
    pos = np.zeros((slots,), np.int32)
    stride = max(1, slots // max(len(ctx), 1))
    at = 0
    for i, x in enumerate(ctx):
        n = -(-(x + w) // t)
        slot = min(i * stride, slots - len(ctx) + i)
        tbl[slot, :n] = ids[at:at + n]
        pos[slot] = x
        at += n
    return qt, qr, c_pool, r_pool, jnp.asarray(tbl), jnp.asarray(pos)


def device_us(fn, args, name):
    """Median device time of the custom calls named ``name`` in two traced
    launches of ``fn`` (compiled and run once before)."""
    run = jax.jit(fn)
    jax.block_until_ready(run(*args))
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(2):
            jax.block_until_ready(run(*args))
        jax.profiler.stop_trace()
        planes = load_planes(find_xplane(d))
    durs = [dur for pn, lines in planes.items() if is_device_plane(pn)
            for ev, _, dur in lines.get("XLA Ops", []) if name in ev and "custom-call" in ev]
    if not durs:
        raise RuntimeError(f"no event named {name}")
    return float(np.median(durs)) / 1e3, len(durs)


def launch(call, layers):
    """STEPS x layers calls in one program, each fed by the one before."""
    def fn(qt, qr, c_pool, r_pool, tbl, pos):
        live = live_slots(tbl[:, 0] > 0)

        def step(q, i):
            o = call(q, qr, c_pool, r_pool, tbl, pos, live, i % layers, 0.07)
            return q + (o * 1e-3).astype(q.dtype), None
        return jax.lax.scan(step, qt, jnp.arange(STEPS * layers, dtype=jnp.int32))[0]
    return fn


def reading(out, cell, slots, ctx, tokens, variant="kernel", **split):
    c = CELLS[cell]
    k = tokens // c["t"]
    args = inputs(cell, slots, ctx)
    if variant == "kernel":
        M._run_entries = lambda *a: k
        call, name = M.mla_paged_decode_attention, "mla_paged_decode_attention"
    else:
        call, name = functools.partial(old_call, k=k, **split), "mla_sweep_split"
    try:
        us, n = device_us(launch(call, c["layers"]), args, name)
    except Exception as e:  # a run length Mosaic refuses is a reading too
        print(f"{cell} slots {slots} live {len(ctx)} run {tokens}: {type(e).__name__}: "
              f"{str(e)[:200]}", flush=True)
        return
    blocks = sum(x // c["t"] + 1 for x in ctx)
    runs = sum(x // c["t"] // k + 1 for x in ctx)
    if variant != "kernel":
        runs += slots - len(ctx)  # a slot without a request: one run of the null block
    mib = blocks * c["t"] * (R + DR) * 2 / 2**20
    row = dict(cell=cell, variant=variant if variant == "kernel" else json.dumps(split),
               slots=slots, live=len(ctx), run_tokens=tokens, runs=runs, live_mib=mib,
               us_a_call=us, events=n, us_a_mib=us / mib if mib else None,
               share_of_819=mib * 2**20 / 819e9 * 1e6 / us if mib else None)
    out.append(row)
    print(json.dumps(row), flush=True)


def check(cell, w=1):
    """The kernel as the package sizes it against the XLA form over the
    gathered view, in the cell's bf16, ``w`` query positions a slot: the
    largest difference over the live rows beside the largest value, and
    whether the empty rows are zeros."""
    c = CELLS[cell]
    M._run_entries = RULE
    ctx = c["ctx"][:-1] + [c["nb"] * c["t"] - w]  # one row to the table's end
    qt, qr, c_pool, r_pool, tbl, pos = inputs(cell, c["slots"], ctx, seed=3, w=w)
    live = live_slots(tbl[:, 0] > 0)
    got = jax.jit(lambda *a: M.mla_paged_decode_attention(*a, live, 1, 0.07))(
        qt, qr, c_pool, r_pool, tbl, pos)

    @jax.jit
    def xla(qt, qr, c_pool, r_pool, tbl, pos):
        view = lambda pool: pool[tbl, 1, 0].reshape(tbl.shape[0], -1, pool.shape[-1])  # noqa: E731
        return M.mla_absorbed_attention(qt, qr, view(c_pool), view(r_pool),
                                        pos[:, None] + jnp.arange(w)[None], 0.07)

    want = xla(qt, qr, c_pool, r_pool, tbl, pos)
    on = np.asarray(live.mask)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    print(f"check {cell} w {w}: run of {M._run_entries(c['t'], c['nb'], (R + DR) * 2)} entries, "
          f"max |kernel - xla| {np.abs(got[on] - want[on]).max():.4f} beside max |xla| "
          f"{np.abs(want[on]).max():.3f}, finite {bool(np.isfinite(got).all())}, "
          f"empty rows zero {not got[~on].any()}", flush=True)


def fit(rows, cell):
    rs = [r for r in rows if r["cell"] == cell and r["variant"] == "kernel"]
    if len(rs) < 4:
        return
    a = np.array([[r["runs"], r["live"], r["live_mib"]] for r in rs])
    y = np.array([r["us_a_call"] for r in rs])
    coef, res, *_ = np.linalg.lstsq(a, y, rcond=None)
    err = a @ coef - y
    print(f"fit {cell}: us = {coef[0]:.3f} x runs + {coef[1]:.3f} x live slots + "
          f"{coef[2]:.3f} x MiB   (rms error {np.sqrt(np.mean(err ** 2)):.2f} us over "
          f"{len(rs)} readings)", flush=True)


def main():
    parts = sys.argv[1:] or ["check", "run", "ctx", "split"]
    out = []
    print(jax.devices()[0].device_kind, flush=True)
    for cell, c in CELLS.items():
        ctx, slots = c["ctx"], c["slots"]
        if "check" in parts:
            for w in (1, 7):  # decode, and the width a speculative verify passes
                check(cell, w)
        if "rule" in parts:  # the package's own run length, all slots and the live ones alone
            tokens = RULE(c["t"], c["nb"], (R + DR) * 2) * c["t"]
            reading(out, cell, slots, ctx, tokens)
            reading(out, cell, len(ctx), ctx, tokens)
            reading(out, cell, slots, [x // 2 for x in ctx], tokens)
        if "run" in parts:
            for tokens in (128, 256, 512, 1024, 2048):
                for s in sorted({len(ctx), (len(ctx) + slots) // 2, slots}):
                    reading(out, cell, s, ctx, tokens)
        if "ctx" in parts:  # the same slots, other bytes: half the rows, half the contexts
            for tokens in (256, 1024):
                reading(out, cell, slots, ctx[: len(ctx) // 2], tokens)
                reading(out, cell, slots, [x // 2 for x in ctx], tokens)
                reading(out, cell, slots, [], tokens)
        if "split" in parts:
            for tokens in (256, 1024):
                for split in (dict(dma=True, compute=True, mask=True),
                              dict(dma=True, compute=True, mask=False),
                              dict(dma=True, compute=False, mask=False),
                              dict(dma=False, compute=True, mask=True),
                              dict(dma=False, compute=True, mask=False)):
                    reading(out, cell, slots, ctx, tokens, "split", **split)
        fit(out, cell)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/mla_kernel_sweep.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
