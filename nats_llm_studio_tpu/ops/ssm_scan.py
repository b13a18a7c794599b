"""The state-space (Mamba-2 / SSD) scan: its chunked form for prefill, its
one-step form for decode, and the causal convolution with its carried tail.

One layer, one token, head h of width P, state width N:

    a = exp(dt_h A_h)            S[h, p, n] <- a S[h, p, n] + dt_h x[h, p] B[n]
    y[h, p] = sum_n C[n] S[h, p, n]              (+ D_h x[h, p], the caller's)

B and C are one group's, [N] a token, or G groups', [G, N] a token: head h
then reads group h // (H / G).

**Prefill** (``ssd_chunked``) computes the same in chunks of Q tokens: inside
a chunk the outputs are one masked [Q, Q] product a head (the decays between
two positions of a chunk are exp of a difference of cumulative sums), the
chunk's contribution to the state is one product over its positions, and the
state is carried from chunk to chunk. A position with dt = 0 neither decays
nor feeds the state: that is how padding is left out.

**Decode** (``ssm_state_step``) is ONE Pallas kernel over the state pool
``[slots, layers, H / k, N, k P]``, aliased onto its output: a grid cell reads
a slot's block of heads, updates it and writes it back to the same place, so
a step copies nothing. Which slots it touches is data: ``live_slots`` lists
the slots that hold a decoding request, once a launch, and the list rides in
as scalars beside the layer. Grid place g is slot ``order[g]``; a place past
the list names the block the place before it named, which is neither fetched
nor written again, so a step moves the LIVE slots' state once in and once out
and a slot without a request comes out bit for bit as it went in. The
pool's minor plane is [N, k P] with k = 128 / P heads side by side on the 128
lanes (P = 64: two heads a row): the decay and dt x are then plain lane rows,
B and C columns, the update a broadcast multiply-add and the read-out a sum
over sublanes, none of which needs a relayout in the kernel. ``pack_state`` /
``unpack_state`` go between that plane and the [H, P, N] of the equations.

**The convolution** is depthwise and causal over K inputs. The pool keeps a
slot's last K raw inputs (K - 1 are needed to go on; the K-th lets the last
prompt position be replayed, which the batcher does for a request that wants
its first token masked or with log-probabilities).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .moe_experts import hit_list

LANES = 128
# what one grid cell's block of a slot's state may take: 1 MiB, double
# buffered in and out is 4 MiB of a v5e core's 16 MiB of scoped VMEM
_STATE_BLOCK_BYTES = 1 << 20


def heads_per_row(n_heads: int, head_dim: int) -> int:
    """k: heads laid side by side on the lanes of one state row."""
    if head_dim < LANES and LANES % head_dim == 0 and n_heads % (LANES // head_dim) == 0:
        return LANES // head_dim
    return 1


def state_plane(n_heads: int, head_dim: int, d_state: int) -> tuple[int, int, int]:
    """(H / k, N, k P): a slot's state of one layer as the pool holds it."""
    k = heads_per_row(n_heads, head_dim)
    return n_heads // k, d_state, k * head_dim


def pack_state(s: jax.Array, k: int) -> jax.Array:
    """[..., H, P, N] -> [..., H / k, N, k P]."""
    *lead, h, p, n = s.shape
    s = s.reshape(*lead, h // k, k, p, n)
    s = jnp.moveaxis(s, -1, -3)  # [..., H/k, N, k, P]
    return s.reshape(*lead, h // k, n, k * p)


def unpack_state(s: jax.Array, k: int) -> jax.Array:
    """[..., H / k, N, k P] -> [..., H, P, N]."""
    *lead, hk, n, kp = s.shape
    s = s.reshape(*lead, hk, n, k, kp // k)
    s = jnp.moveaxis(s, -3, -1)  # [..., H/k, k, P, N]
    return s.reshape(*lead, hk * k, kp // k, n)


# ---------------------------------------------------------------------------
# the causal convolution
# ---------------------------------------------------------------------------


def causal_conv(xbc: jax.Array, tail: jax.Array, w: jax.Array, b: jax.Array | None,
                valid: jax.Array) -> tuple[jax.Array, jax.Array]:
    """silu(depthwise causal conv + bias) over ``xbc`` [B, T, C] that goes on
    from ``tail`` [K, B, C], the K raw inputs before it a tap a plane (zeros
    at a start), as the pools keep them. ``w`` [K, C]: w[K - 1] weighs the
    position itself; ``b`` None: no bias (the gated-delta-rule layers').
    ``valid`` [B]: how many of a row's T positions are real; the new tail is
    the K raw inputs ending at the last real one (the old tail where none
    is)."""
    k = w.shape[0]
    t = xbc.shape[1]
    # [B, K + T, C]: a chunk's few rows of tail beside its T positions
    ext = jnp.concatenate([jnp.swapaxes(tail, 0, 1).astype(xbc.dtype), xbc], axis=1)
    wf = w.astype(jnp.float32)
    bias = None if b is None else b.astype(jnp.float32)
    out = sum(wf[j] * ext[:, 1 + j: 1 + j + t].astype(jnp.float32) for j in range(k))
    if bias is not None:
        out = bias + out
    new_tail = jax.vmap(lambda e, v: jax.lax.dynamic_slice_in_dim(e, v, k, axis=0),
                        out_axes=1)(ext, valid)
    return jax.nn.silu(out).astype(xbc.dtype), new_tail.astype(tail.dtype)


def conv_step(xbc: jax.Array, tail: jax.Array, w: jax.Array, b: jax.Array | None,
              fresh: jax.Array) -> tuple[jax.Array, jax.Array]:
    """One position a row: ``xbc`` [B, C], ``tail`` [K, B, C], a tap a plane
    with the rows on the sublanes (K = 4 taps there are a quarter of a bf16
    tile, and the device gathers them row by row). A ``fresh`` row shifts its
    input in as the last plane; a row that replays its last position finds it
    there already and reads the tail as it is."""
    shifted = jnp.concatenate([tail[1:], xbc[None].astype(tail.dtype)], axis=0)
    tail = jnp.where(fresh[None, :, None], shifted, tail)
    bias = None if b is None else b.astype(jnp.float32)
    out = jnp.sum(w.astype(jnp.float32)[:, None] * tail.astype(jnp.float32), axis=0)
    if bias is not None:
        out = bias + out
    return jax.nn.silu(out).astype(xbc.dtype), tail


# ---------------------------------------------------------------------------
# prefill: the chunked form
# ---------------------------------------------------------------------------


def ssd_chunked(x: jax.Array, dt: jax.Array, a: jax.Array, bm: jax.Array, cm: jax.Array,
                s0: jax.Array, chunk: int) -> tuple[jax.Array, jax.Array]:
    """The recurrence over T positions in chunks of ``chunk``.

    x [B, T, H, P]; dt [B, T, H] f32 >= 0 (0 at a position that is not
    real); a [H] f32 < 0; bm, cm [B, T, N], or [B, T, G, N] for G groups of
    H / G heads each; s0 [B, H, P, N] f32, the state before position 0.
    Returns (y [B, T, H, P] f32, the state after the last position
    [B, H, P, N] f32). T is padded to whole chunks with dt = 0."""
    b, t, h, p = x.shape
    if bm.ndim == 4:  # a group is the one-group recurrence over its own heads
        g = bm.shape[2]

        def split(z, axis):  # heads -> [G, H / G]
            return z.reshape(z.shape[:axis] + (g, h // g) + z.shape[axis + 1:])

        y, s = jax.vmap(lambda *group: ssd_chunked(*group, chunk),
                        in_axes=(2, 2, 0, 2, 2, 1), out_axes=(2, 1))(
            split(x, 2), split(dt, 2), split(a, 0), bm, cm, split(s0, 1))
        return y.reshape(b, t, h, p), s.reshape(s0.shape)
    q = min(chunk, t)
    pad = -t % q
    if pad:
        x, dt, bm, cm = (jnp.pad(z, [(0, 0), (0, pad)] + [(0, 0)] * (z.ndim - 2))
                         for z in (x, dt, bm, cm))
    nc = (t + pad) // q
    xf = x.astype(jnp.float32).reshape(b, nc, q, h, p)
    dt = dt.astype(jnp.float32).reshape(b, nc, q, h)
    bf = bm.astype(jnp.float32).reshape(b, nc, q, -1)
    cf = cm.astype(jnp.float32).reshape(b, nc, q, -1)
    cs = jnp.cumsum(dt * a.astype(jnp.float32), axis=2)  # [B, nc, Q, H], <= 0 and falling
    dtx = xf * dt[..., None]
    # inside a chunk: y[t] = sum_{s <= t} (C_t . B_s) exp(cs_t - cs_s) dt_s x_s
    g = jnp.einsum("bcqn,bcsn->bcqs", cf, bf)
    diff = cs[:, :, :, None, :] - cs[:, :, None, :, :]  # [B, nc, Q(t), Q(s), H]
    tril = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None]
    m = g[..., None] * jnp.exp(jnp.where(tril, diff, -jnp.inf))
    y = jnp.einsum("bcqsh,bcshp->bcqhp", m, dtx)
    # what a chunk adds to the state, decayed to the chunk's end
    to_end = jnp.exp(cs[:, :, -1:, :] - cs)  # [B, nc, Q, H]
    s_add = jnp.einsum("bcqh,bcqhp,bcqn->bchpn", to_end, dtx, bf)
    through = jnp.exp(cs[:, :, -1, :])  # [B, nc, H]: a whole chunk's decay

    def carry(s, xs):
        add, thr = xs
        return thr[..., None, None] * s + add, s

    s_end, s_in = jax.lax.scan(
        carry, s0.astype(jnp.float32),
        (jnp.moveaxis(s_add, 1, 0), jnp.moveaxis(through, 1, 0)))
    s_in = jnp.moveaxis(s_in, 0, 1)  # [B, nc, H, P, N]: the state a chunk starts from
    y = y + jnp.einsum("bcqn,bchpn,bcqh->bcqhp", cf, s_in, jnp.exp(cs))
    return y.reshape(b, nc * q, h, p)[:, :t], s_end


# ---------------------------------------------------------------------------
# decode: one step over the state pool, in place
# ---------------------------------------------------------------------------


def _heads_block(rows: int, n: int, lanes: int) -> int:
    """Rows of heads one grid cell takes: the largest divisor of ``rows`` (the
    rows of ONE group: a cell reads one B and one C) whose [rows, N, lanes]
    f32 block stays under ``_STATE_BLOCK_BYTES``."""
    cap = max(1, _STATE_BLOCK_BYTES // (n * lanes * 4))
    return next(r for r in range(min(cap, rows), 0, -1) if rows % r == 0)


class LiveSlots(NamedTuple):
    """The slots of the state pool a decode launch touches: ``mask`` [slots]
    bool, ``order`` [slots] int32 (the live slots in rising order, then the
    last of them again in every place past ``n``) and ``n`` int32."""
    mask: jax.Array
    order: jax.Array
    n: jax.Array


def live_slots(mask: jax.Array) -> LiveSlots:
    """The list ``ssm_state_step`` prefetches, from ``mask`` [slots] bool.
    Made once a launch: the layers of a step all take the same one."""
    order, n = hit_list(mask, mask.shape[0])
    return LiveSlots(mask, order, n)


def _step_kernel(hb, lanes, layer_ref, order_ref, n_ref, a_ref, u_ref, bc_ref,
                 s_ref, so_ref, y_ref):
    """Grid (places, blocks of head rows): place g is slot ``order[g]``.
    a, u: [1, hb x lanes] rows (decay and dt x of the block's heads); bc:
    [N, 2] (B and C as columns); s: the block [hb, N, lanes] of the slot's
    state in this layer. A place past ``n`` does nothing: its blocks are the
    ones the place before it named, still in VMEM and written back once."""
    del layer_ref, order_ref
    g, n = pl.program_id(0), n_ref[0]

    @pl.when(g < n)
    def _live():
        bcol = bc_ref[:, 0:1]
        ccol = bc_ref[:, 1:2]
        for j in range(hb):
            at = slice(j * lanes, (j + 1) * lanes)
            s = s_ref[j] * a_ref[:, at] + bcol * u_ref[:, at]
            so_ref[j] = s
            y_ref[:, at] = jnp.sum(s * ccol, axis=0, keepdims=True)

    @pl.when((n == 0) & (g == 0) & (pl.program_id(1) == 0))
    def _nothing_listed():
        # every place names ONE block, and the grid's end writes it back:
        # from what was read, not from a buffer nothing wrote
        so_ref[...] = s_ref[...]


def ssm_state_step(pool: jax.Array, layer, live: LiveSlots, decay: jax.Array,
                   dtx: jax.Array, bm: jax.Array, cm: jax.Array, interpret: bool = False):
    """One position of the ``live`` slots in layer ``layer`` of the state pool
    ``[slots, L, H / k, N, k P]`` f32, in place (the pool is aliased onto the
    result: donate it). ``decay`` [slots, H] = exp(dt A) (1 for a live row that
    must keep its state), ``dtx`` [slots, H, P] = dt x (0 likewise), ``bm``,
    ``cm`` [slots, N], or [slots, G, N] for G groups of H / G heads each.
    Returns (pool, y [slots, H, P] f32 = C . S after the
    update; zeros for a slot that is not live, whose state is not touched)."""
    slots, _, rows, n, lanes = pool.shape
    h, p = dtx.shape[1], dtx.shape[2]
    groups = bm.shape[1] if bm.ndim == 3 else 1
    hb = _heads_block(rows // groups, n, lanes)
    nj = rows // hb
    a_row = jnp.repeat(decay.astype(jnp.float32), p, axis=1).reshape(slots, 1, h * p)
    u_row = dtx.astype(jnp.float32).reshape(slots, 1, h * p)
    bc = jnp.stack([bm, cm], axis=-1).astype(jnp.float32)  # [slots, (G,) N, 2]

    def at(g, j, order_ref, n_ref):  # a place past the list stays on the last block
        return order_ref[g], jnp.where(g < n_ref[0], j, nj - 1)

    def row_map(g, j, layer_ref, order_ref, n_ref):
        slot, j = at(g, j, order_ref, n_ref)
        return (slot, 0, j)

    def bc_map(g, j, layer_ref, order_ref, n_ref):
        if groups == 1:
            return (order_ref[g], 0, 0)
        slot, j = at(g, j, order_ref, n_ref)
        return (slot, j * hb * groups // rows, 0, 0)  # the block's own group

    def state_map(g, j, layer_ref, order_ref, n_ref):
        slot, j = at(g, j, order_ref, n_ref)
        return (slot, layer_ref[0], j, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(slots, nj),
        in_specs=[pl.BlockSpec((None, 1, hb * lanes), row_map),
                  pl.BlockSpec((None, 1, hb * lanes), row_map),
                  pl.BlockSpec((None,) * (bc.ndim - 2) + (n, 2), bc_map),
                  pl.BlockSpec((None, None, hb, n, lanes), state_map)],
        out_specs=[pl.BlockSpec((None, None, hb, n, lanes), state_map),
                   pl.BlockSpec((None, 1, hb * lanes), row_map)],
    )
    pool, y = pl.pallas_call(
        lambda *refs: _step_kernel(hb, lanes, *refs),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((slots, 1, h * p), jnp.float32)],
        # operand 6 (after the prefetched layer and list) is the pool; result
        # 0 is it again
        input_output_aliases={6: 0},
        # a block is revisited along both axes: neither may be split
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        # a constant: the custom call's name in a device trace
        name="ssm_state_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), live.order,
      jnp.asarray(live.n, jnp.int32).reshape(1), a_row, u_row, bc, pool)
    # the rows of y no place named hold whatever the buffer held
    return pool, jnp.where(live.mask[:, None, None], y.reshape(slots, h, p), 0.0)


def ssm_state_step_auto(pool, layer, live, decay, dtx, bm, cm):
    """The kernel, through the Pallas interpreter off-TPU."""
    return ssm_state_step(pool, layer, live, decay, dtx, bm, cm,
                          interpret=jax.default_backend() != "tpu")
