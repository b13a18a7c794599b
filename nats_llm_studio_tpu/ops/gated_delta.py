"""The gated delta rule (Gated DeltaNet's linear attention): its chunked form
for prefill and its one-step form for decode, beside ``ops/ssm_scan.py``,
whose convolution and live-slot list it uses as they are.

One layer, one token, value head h with a state S [d_k, d_v] in float32
(alpha the head's decay, beta its write strength, k and q L2-normalised):

    S' = alpha S                       (decay FIRST)
    u  = beta (v - S'^T k)             (what the decayed state lacks of v: read BEFORE the write)
    S  = S' + k u^T
    o  = S^T q                         (read AFTER the write)

Mamba-2's step writes an outer product that does not depend on the state;
this one's does, so a chunk is not a sum of independent outer products.

**Prefill** (``gated_delta_chunked``) computes the same in chunks of C tokens.
With G_t the running sum of log alpha inside a chunk and S_0 the state the
chunk starts from,

    S'_t = e^{G_t} S_0 + sum_{s<t} e^{G_t - G_s} k_s u_s^T
    (I + A) U = beta (V - e^G (K S_0)),   A[t, s] = beta_t e^{G_t - G_s} (k_t . k_s), s < t

one unit lower-triangular system a chunk and head. Its right side is linear
in S_0, so the solve runs ONCE for all chunks on [beta V | beta e^G K] and
the scan that carries the state from chunk to chunk holds only products:
U = U_0 - W S_0, o_t = e^{G_t} S_0^T q_t + sum_{s<=t} e^{G_t - G_s} (k_s . q_t) u_s,
S_C = e^{G_C} S_0 + sum_s e^{G_C - G_s} k_s u_s^T. Every decay is the exp of
a difference that is <= 0: nothing overflows however fast a head forgets. A
position with log alpha = 0 and beta = 0 neither decays nor writes: that is
how padding is left out. Plain XLA.

**Decode** (``gated_delta_step``) is one Pallas kernel over the state pool
``[slots, layers, H_v, d_k, d_v]``, aliased onto its output, that moves only
the slots that hold a request (``ssm_scan.LiveSlots``: grid place g is slot
``order[g]``, a place past the list names the block before it again, which is
neither fetched nor written twice). The minor plane is [d_k, d_v] with d_v on
the lanes: k and q ride in as columns, v, alpha and beta as lane rows, so the
two reads are sums over sublanes and the write a broadcast multiply-add.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ssm_scan import LiveSlots, _heads_block

_HI = jax.lax.Precision.HIGHEST
# the chunked rule's tile: tokens solved together (one [C, C] unit-triangular
# system a chunk and head); a size of the computation, not of a model
CHUNK = 64


def l2_normalise(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """x / sqrt(sum x^2 + eps) over the last axis, float32."""
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + eps)


def gated_delta_recurrent(q, k, v, log_alpha, beta, s0):
    """The rule one token at a time, as it is written (what the chunked form
    and the kernel are held to): q, k [B, T, H, dk], v [B, T, H, dv],
    log_alpha, beta [B, T, H], s0 [B, H, dk, dv]. Returns (o [B, T, H, dv]
    f32, the state after the last position)."""
    def step(s, xs):
        qt, kt, vt, gt, bt = xs
        s = jnp.exp(gt)[..., None, None] * s
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", s, kt, precision=_HI))
        s = s + kt[..., :, None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, qt, precision=_HI)

    xs = tuple(jnp.moveaxis(z.astype(jnp.float32), 1, 0) for z in (q, k, v, log_alpha, beta))
    s, o = jax.lax.scan(step, s0.astype(jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1), s


def gated_delta_chunked(q, k, v, log_alpha, beta, s0, chunk: int = CHUNK):
    """The rule over T positions in chunks of ``chunk``: shapes as
    ``gated_delta_recurrent``; log_alpha <= 0 and beta in [0, 1], both 0 at a
    position that is not real. T is padded to whole chunks the same way."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    c = min(chunk, t)
    pad = -t % c
    if pad:
        q, k, v, log_alpha, beta = (
            jnp.pad(z, [(0, 0), (0, pad)] + [(0, 0)] * (z.ndim - 2))
            for z in (q, k, v, log_alpha, beta))
    nc = (t + pad) // c

    def heads_major(z):  # [B, T, H, ...] -> [B, nc, H, C, ...]
        z = z.astype(jnp.float32).reshape((b, nc, c, h) + z.shape[3:])
        return jnp.moveaxis(z, 3, 2)

    qf, kf, vf, g, bt = (heads_major(z) for z in (q, k, v, log_alpha, beta))
    gs = jnp.cumsum(g, axis=-1)  # [B, nc, H, C], <= 0 and falling
    diff = gs[..., :, None] - gs[..., None, :]  # [.., C(t), C(s)]
    idx = jnp.arange(c)
    below = idx[:, None] > idx[None, :]
    decay = jnp.exp(jnp.where(idx[:, None] >= idx[None, :], diff, -jnp.inf))  # s <= t, else 0
    kk = jnp.einsum("bnhtk,bnhsk->bnhts", kf, kf, precision=_HI)
    a = jnp.where(below, bt[..., :, None] * decay * kk, 0.0)
    rhs = jnp.concatenate([bt[..., None] * vf, (bt * jnp.exp(gs))[..., None] * kf], axis=-1)
    sol = jax.scipy.linalg.solve_triangular(
        a + jnp.eye(c, dtype=jnp.float32), rhs, lower=True, unit_diagonal=True)
    u0, w = sol[..., :dv], sol[..., dv:]  # U = u0 - w S_0
    qk = jnp.einsum("bnhtk,bnhsk->bnhts", qf, kf, precision=_HI) * decay  # s <= t
    to_end = jnp.exp(gs[..., -1:] - gs)  # [B, nc, H, C]
    through = jnp.exp(gs[..., -1])       # [B, nc, H]

    def carry(s, xs):
        u0, w, qk, qf, kf, eg, to_end, through = xs
        u = u0 - jnp.einsum("bhtk,bhkv->bhtv", w, s, precision=_HI)
        o = (eg[..., None] * jnp.einsum("bhtk,bhkv->bhtv", qf, s, precision=_HI)
             + jnp.einsum("bhts,bhsv->bhtv", qk, u, precision=_HI))
        s = (through[..., None, None] * s
             + jnp.einsum("bhtk,bhtv->bhkv", kf * to_end[..., None], u, precision=_HI))
        return s, o

    xs = tuple(jnp.moveaxis(z, 1, 0) for z in (u0, w, qk, qf, kf, jnp.exp(gs), to_end, through))
    s_end, o = jax.lax.scan(carry, s0.astype(jnp.float32), xs)
    o = jnp.moveaxis(o, 0, 1)  # [B, nc, H, C, dv]
    return jnp.moveaxis(o, 2, 3).reshape(b, nc * c, h, dv)[:, :t], s_end


# ---------------------------------------------------------------------------
# decode: one step over the state pool, in place
# ---------------------------------------------------------------------------


def gated_delta_step_xla(pool, layer, live: LiveSlots, decay, beta, q, k, v):
    """``gated_delta_step`` in plain XLA over every slot (what the kernel is
    held to): a slot that is not live keeps its state and reads zeros."""
    s = jax.lax.dynamic_index_in_dim(pool, layer, axis=1, keepdims=False)
    o, s1 = gated_delta_recurrent(
        q[:, None], k[:, None], v[:, None], jnp.log(decay)[:, None], beta[:, None], s)
    on = live.mask[:, None, None, None]
    pool = jax.lax.dynamic_update_index_in_dim(pool, jnp.where(on, s1, s), layer, axis=1)
    return pool, jnp.where(live.mask[:, None, None], o[:, 0], 0.0)


def _step_kernel(hb, lanes, layer_ref, order_ref, n_ref, a_ref, b_ref, v_ref, kq_ref,
                 s_ref, so_ref, y_ref):
    """Grid (places, blocks of heads): place g is slot ``order[g]``. a, b, v:
    [1, hb x lanes] rows (decay, write strength, value of the block's heads);
    kq: [d_k, 2 hb] (the heads' k as columns, then their q); s: the block
    [hb, d_k, lanes] of the slot's state in this layer. A place past ``n``
    does nothing: its blocks are the ones the place before it named, still in
    VMEM and written back once."""
    del layer_ref, order_ref
    g, n = pl.program_id(0), n_ref[0]

    @pl.when(g < n)
    def _live():
        for j in range(hb):
            at = slice(j * lanes, (j + 1) * lanes)
            kcol = kq_ref[:, j:j + 1]
            qcol = kq_ref[:, hb + j:hb + j + 1]
            s = s_ref[j] * a_ref[:, at]
            u = b_ref[:, at] * (v_ref[:, at] - jnp.sum(s * kcol, axis=0, keepdims=True))
            s = s + kcol * u
            so_ref[j] = s
            y_ref[:, at] = jnp.sum(s * qcol, axis=0, keepdims=True)

    @pl.when((n == 0) & (g == 0) & (pl.program_id(1) == 0))
    def _nothing_listed():
        # every place names ONE block, and the grid's end writes it back:
        # from what was read, not from a buffer nothing wrote
        so_ref[...] = s_ref[...]


def gated_delta_step(pool: jax.Array, layer, live: LiveSlots, decay: jax.Array,
                     beta: jax.Array, q: jax.Array, k: jax.Array, v: jax.Array,
                     interpret: bool = False):
    """One position of the ``live`` slots in layer ``layer`` of the state pool
    ``[slots, L, H, d_k, d_v]`` f32, in place (the pool is aliased onto the
    result: donate it). ``decay`` [slots, H] = alpha (1 for a live row that
    must keep its state), ``beta`` [slots, H] (0 likewise), ``q``, ``k``
    [slots, H, d_k] (a key head repeated for the value heads it serves), ``v``
    [slots, H, d_v]. Returns (pool, o [slots, H, d_v] f32 = S^T q after the
    write; zeros for a slot that is not live, whose state is not touched)."""
    slots, _, h, dk, lanes = pool.shape
    hb = _heads_block(h, dk, lanes)
    nj = h // hb

    def row(x):  # [slots, H] or [slots, H, d_v] -> [slots, 1, H x d_v] f32
        x = x.astype(jnp.float32)
        if x.ndim == 2:
            x = jnp.repeat(x, lanes, axis=1)
        return x.reshape(slots, 1, h * lanes)

    # k and q as COLUMNS, a block's heads side by side on the lanes: [slots,
    # blocks, d_k, 2 hb] (a [.., d_k, 2] plane a head would be padded to 128
    # lanes, 64 times its bytes and as many as the state itself)
    kq = jnp.concatenate([z.astype(jnp.float32).reshape(slots, nj, hb, dk)
                          for z in (k, q)], axis=2)
    kq = jnp.swapaxes(kq, 2, 3)

    def at(g, j, order_ref, n_ref):  # a place past the list stays on the last block
        return order_ref[g], jnp.where(g < n_ref[0], j, nj - 1)

    def row_map(g, j, layer_ref, order_ref, n_ref):
        slot, j = at(g, j, order_ref, n_ref)
        return (slot, 0, j)

    def kq_map(g, j, layer_ref, order_ref, n_ref):
        slot, j = at(g, j, order_ref, n_ref)
        return (slot, j, 0, 0)

    def state_map(g, j, layer_ref, order_ref, n_ref):
        slot, j = at(g, j, order_ref, n_ref)
        return (slot, layer_ref[0], j, 0, 0)

    rows = pl.BlockSpec((None, 1, hb * lanes), row_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(slots, nj),
        in_specs=[rows, rows, rows,
                  pl.BlockSpec((None, None, dk, 2 * hb), kq_map),
                  pl.BlockSpec((None, None, hb, dk, lanes), state_map)],
        out_specs=[pl.BlockSpec((None, None, hb, dk, lanes), state_map), rows],
    )
    pool, y = pl.pallas_call(
        lambda *refs: _step_kernel(hb, lanes, *refs),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((slots, 1, h * lanes), jnp.float32)],
        # operand 7 (after the prefetched layer and list) is the pool; result
        # 0 is it again
        input_output_aliases={7: 0},
        # a block is revisited along both axes: neither may be split
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        # a constant: the custom call's name in a device trace
        name="gated_delta_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), live.order,
      jnp.asarray(live.n, jnp.int32).reshape(1), row(decay), row(beta), row(v), kq, pool)
    # the rows of y no place named hold whatever the buffer held
    return pool, jnp.where(live.mask[:, None, None], y.reshape(slots, h, lanes), 0.0)


def gated_delta_step_auto(pool, layer, live, decay, beta, q, k, v):
    """The kernel, through the Pallas interpreter off-TPU."""
    return gated_delta_step(pool, layer, live, decay, beta, q, k, v,
                            interpret=jax.default_backend() != "tpu")
