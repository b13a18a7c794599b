"""Lightning Attention (linear attention with a constant decay a head): its
chunked form for prefill and its one-step form for decode, beside
``ops/gated_delta.py`` and ``ops/ssm_scan.py``, whose live-slot list it takes
as it is.

One layer, one token, head h with a state S [d_k, d_v] in float32 and a decay
lambda = exp(-a) that is a constant of (layer, head):

    S = lambda S + k v^T
    o = S^T q                          (read AFTER the write)

No delta term, no gate that depends on the token, no convolution: the write
does not depend on the state, so a chunk IS a sum of independent outer
products and its three products run on the MXU.

The rates ``a`` come from a leaf that holds them as logits (``rates``:
a = sigmoid(leaf), in (0, 1) whatever the leaf holds, so lambda < 1 and
nothing grows): the initialiser writes the published table there
(``decay_table``), the benchmark's seeded weights draw the leaf like any
other, a loader would put the logits of a checkpoint's table.

**Prefill** (``lightning_chunked``) in chunks of C tokens. With S_0 the state
a chunk starts from and r its real positions (a row's padding writes nothing
and does not decay the state):

    O   = (Q K^T . L) V + diag(lambda^(i+1)) Q S_0,   L[i, j] = lambda^(i-j), i >= j
    S_r = lambda^r S_0 + (diag(lambda^(r-1-j)) K)^T V  over j < r

Q K^T, (.) V and K^T V take the operands in the serving dtype and accumulate
in float32; the decays, the state and the product that reads it (Q S_0) are
float32. Every decay is the exp of something <= 0. Plain XLA.

**Decode** (``lightning_step``, ``lightning_step`` in a device trace) is one
Pallas call a layer a step over the state pool ``[slots, layers, H, d_k,
d_v]``, aliased onto its output, that moves only the slots that hold a
request (``ssm_scan.LiveSlots``), as ``gated_delta.gated_delta_step`` does:
k and q of a block's heads come in as rows and are transposed to columns in
VMEM, v rides in as lane rows, the decay as scalars, so the write is a
broadcast multiply-add and the read a sum over sublanes. ``lightning_step_xla``
is the same in plain XLA over every slot: what the kernel is held to, run by
no program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ssm_scan import LiveSlots, _heads_block

_HI = jax.lax.Precision.HIGHEST
# the chunked form's tile: one pass of the 128-wide MXU a product; a size of
# the computation, not of a model
CHUNK = 128


def decay_table(layers, n_heads: int, depth: int) -> jax.Array:
    """Lightning Attention's rates a[l, h] = 2^(-8 (h + 1) / H) (1 - l / (L - 1)
    + 1e-5) for the published layer indices ``layers`` of a stack ``depth``
    deep, as the LOGITS the ``decay`` leaf holds: [len(layers), n_heads] f32."""
    slope = 2.0 ** (-8.0 * (jnp.arange(n_heads, dtype=jnp.float32) + 1) / n_heads)
    depth_term = 1.0 - jnp.asarray(layers, jnp.float32) / max(depth - 1, 1) + 1e-5
    a = slope[None, :] * depth_term[:, None]
    return jnp.log(a) - jnp.log1p(-a)


def rates(leaf: jax.Array) -> jax.Array:
    """a = sigmoid(leaf) in float32: the decay a token is exp(-a)."""
    return jax.nn.sigmoid(leaf.astype(jnp.float32))


def lightning_recurrent(q, k, v, a, real, s0):
    """The rule one token at a time, as it is written (what the chunked form
    and the kernel are held to): q, k [B, T, H, dk], v [B, T, H, dv], a [H]
    rates, real [B, T] bool (a position that is not real leaves the state as
    it is), s0 [B, H, dk, dv]. Returns (o [B, T, H, dv] f32, the state after
    the last real position)."""
    lam = jnp.exp(-a.astype(jnp.float32))[None, :, None, None]

    def step(s, xs):
        qt, kt, vt, on = xs
        s1 = lam * s + kt[..., :, None] * vt[..., None, :]
        s = jnp.where(on[:, None, None, None], s1, s)
        return s, jnp.einsum("bhkv,bhk->bhv", s, qt, precision=_HI)

    xs = tuple(jnp.moveaxis(z.astype(jnp.float32), 1, 0) for z in (q, k, v)) + (
        jnp.moveaxis(real, 1, 0),)
    s, o = jax.lax.scan(step, s0.astype(jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1), s


def lightning_chunked(q, k, v, a, valid, s0, chunk: int = CHUNK):
    """The rule over T positions in chunks of ``chunk``: q, k [B, T, H, dk]
    and v [B, T, H, dv] in the serving dtype, a [H] f32 rates, ``valid`` [B]
    int32 the rows' real positions (the first ``valid`` of T), s0 [B, H, dk,
    dv] f32. Returns (o [B, T, H, dv] f32, the state after each row's last
    real position). What a row computes at a position that is not real is
    finite and nobody's."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    dt = q.dtype
    f32 = jnp.float32
    c = min(chunk, t)
    pad = -t % c
    real = jnp.arange(t + pad, dtype=jnp.int32)[None, :] < valid[:, None]  # [B, T]
    if pad:
        q, k, v = (jnp.pad(z, ((0, 0), (0, pad), (0, 0), (0, 0))) for z in (q, k, v))
    nc = (t + pad) // c
    on = real[..., None, None]
    k, v = jnp.where(on, k, jnp.zeros((), dt)), jnp.where(on, v, jnp.zeros((), dt))

    def heads_major(z):  # [B, T, H, d] -> [nc, B, H, C, d]
        return z.reshape(b, nc, c, h, z.shape[-1]).transpose(1, 0, 3, 2, 4)

    qc, kc, vc = heads_major(q), heads_major(k), heads_major(v)
    a = a.astype(f32)[:, None]  # [H, 1]
    i = jnp.arange(c, dtype=f32)
    lower = i[:, None] >= i[None, :]
    # [H, C, C]: lambda^(i - j) at and under the diagonal, 0 over it
    decay = jnp.exp(jnp.where(lower, -a[..., None] * (i[:, None] - i[None, :]), -jnp.inf))
    qk = jnp.einsum("nbhik,nbhjk->nbhij", qc, kc, preferred_element_type=f32) * decay
    o = jnp.einsum("nbhij,nbhjv->nbhiv", qk.astype(dt), vc, preferred_element_type=f32)
    # the rows' real positions of each chunk, and the decays that read and
    # carry the state
    r = jnp.clip(valid[None, :] - jnp.arange(nc, dtype=jnp.int32)[:, None] * c, 0, c)  # [nc, B]
    rf = r.astype(f32)[..., None, None]  # [nc, B, 1, 1]
    q_in = qc.astype(f32) * jnp.exp(-a * (i + 1.0))[..., None]          # [nc, B, H, C, dk]
    to_end = jnp.exp(-a * jnp.maximum(rf - 1.0 - i, 0.0))               # [nc, B, H, C]
    kd = (kc.astype(f32) * to_end[..., None]).astype(dt)
    through = jnp.exp(-a * rf)[..., None]                               # [nc, B, H, 1, 1]

    def carry(s, xs):
        q_in, kd, vc, through = xs
        inter = jnp.einsum("bhik,bhkv->bhiv", q_in, s, precision=_HI)
        s = through * s + jnp.einsum("bhjk,bhjv->bhkv", kd, vc, preferred_element_type=f32)
        return s, inter

    s_end, inter = jax.lax.scan(carry, s0.astype(f32), (q_in, kd, vc, through))
    o = (o + inter).transpose(1, 0, 3, 2, 4).reshape(b, nc * c, h, dv)
    return o[:, :t], s_end


# ---------------------------------------------------------------------------
# decode: one step over the state pool, in place
# ---------------------------------------------------------------------------


def lightning_step_xla(pool, layer, live: LiveSlots, decay, q, k, v):
    """``lightning_step`` in plain XLA over every slot (what the kernel is
    held to): a slot that is not live keeps its state and reads zeros."""
    s = jax.lax.dynamic_index_in_dim(pool, layer, axis=1, keepdims=False)
    s1 = decay[..., None, None] * s + k[..., :, None] * v[..., None, :]
    o = jnp.einsum("bhkv,bhk->bhv", s1, q, precision=_HI)
    on = live.mask[:, None, None, None]
    pool = jax.lax.dynamic_update_index_in_dim(pool, jnp.where(on, s1, s), layer, axis=1)
    return pool, jnp.where(live.mask[:, None], o.reshape(o.shape[0], -1), 0.0)


def _step_kernel(hb, layer_ref, order_ref, n_ref, decay_ref, q_ref, k_ref, v_ref, s_ref,
                 so_ref, y_ref, kq_ref, o_ref):
    """Grid (places, blocks of heads): place g is slot ``order[g]``. decay:
    [slots, H] scalars; q, k: the block's heads [hb, d_k]; v: [hb, d_v] rows;
    s: the block [hb, d_k, d_v] of the slot's state in this layer. A place
    past ``n`` does nothing: its blocks are the ones the place before it
    named, still in VMEM and written back once. The read-outs S^T q wait in
    ``o`` [blocks, slots to a whole 8, hb x d_v] (zeros at the start), the
    slots on the sublanes; the grid's last cell writes y [slots, H x d_v]
    whole, zeros for a slot no place named."""
    del layer_ref
    g, jj, n = pl.program_id(0), pl.program_id(1), n_ref[0]
    lanes, slots = s_ref.shape[-1], y_ref.shape[0]

    @pl.when((g == 0) & (jj == 0))
    def _first():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(g < n)
    def _live():
        slot = order_ref[g]
        # k and q as COLUMNS, the block's heads side by side on the lanes
        kq_ref[:, :hb] = k_ref[...].T
        kq_ref[:, hb:] = q_ref[...].T
        # a row is stored through its tile of 8: one row at a traced offset is not
        tile = pl.ds(pl.multiple_of(slot // 8 * 8, 8), 8)
        mine = jax.lax.broadcasted_iota(jnp.int32, (8, lanes), 0) == slot % 8
        for j in range(hb):
            at = slice(j * lanes, (j + 1) * lanes)
            s = s_ref[j] * decay_ref[slot, jj * hb + j] + kq_ref[:, j:j + 1] * v_ref[j:j + 1, :]
            so_ref[j] = s
            o = jnp.sum(s * kq_ref[:, hb + j:hb + j + 1], axis=0, keepdims=True)
            o_ref[jj, tile, at] = jnp.where(mine, o, o_ref[jj, tile, at])

    @pl.when((n == 0) & (g == 0) & (jj == 0))
    def _nothing_listed():
        # every place names ONE block, and the grid's end writes it back:
        # from what was read, not from a buffer nothing wrote
        so_ref[...] = s_ref[...]

    @pl.when((g == pl.num_programs(0) - 1) & (jj == pl.num_programs(1) - 1))
    def _read_out():
        for b in range(o_ref.shape[0]):
            y_ref[:, b * hb * lanes:(b + 1) * hb * lanes] = o_ref[b, :slots, :]


def lightning_step(pool: jax.Array, layer, live: LiveSlots, decay: jax.Array, q: jax.Array,
                   k: jax.Array, v: jax.Array, interpret: bool = False):
    """One position of the ``live`` slots in layer ``layer`` of the state pool
    ``[slots, L, H, d_k, d_v]`` f32, in place (the pool is aliased onto the
    result: donate it): ``decay`` [slots, H] = lambda (1 for a live row that
    must keep its state, whose ``v`` is zeros), ``q``, ``k`` [slots, H, d_k]
    and ``v`` [slots, H, d_v] f32. Returns (pool, o [slots, H x d_v] f32 = S^T q
    after the write; zeros for a slot that is not live, whose state is not
    touched)."""
    slots, _, h, dk, lanes = pool.shape
    hb = _heads_block(h, dk, lanes)
    nj = h // hb

    def at(g, j, order_ref, n_ref):  # a place past the list stays on the last block
        return order_ref[g], jnp.where(g < n_ref[0], j, nj - 1)

    def heads_map(g, j, layer_ref, order_ref, n_ref):
        slot, j = at(g, j, order_ref, n_ref)
        return (slot, j, 0)

    def state_map(g, j, layer_ref, order_ref, n_ref):
        slot, j = at(g, j, order_ref, n_ref)
        return (slot, layer_ref[0], j, 0, 0)

    keys = pl.BlockSpec((None, hb, dk), heads_map)
    state = pl.BlockSpec((None, None, hb, dk, lanes), state_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(slots, nj),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), keys, keys,
                  pl.BlockSpec((None, hb, lanes), heads_map), state],
        out_specs=[state, pl.BlockSpec((slots, h * lanes), lambda g, j, *_: (0, 0))],
        scratch_shapes=[pltpu.VMEM((dk, 2 * hb), jnp.float32),
                        pltpu.VMEM((nj, -(-slots // 8) * 8, hb * lanes), jnp.float32)],
    )
    return pl.pallas_call(
        lambda *refs: _step_kernel(hb, *refs),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((slots, h * lanes), jnp.float32)],
        # operand 7 (after the prefetched layer and list) is the pool; result
        # 0 is it again
        input_output_aliases={7: 0},
        # a block is revisited along both axes: neither may be split
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        # a constant: the custom call's name in a device trace
        name="lightning_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), live.order,
      jnp.asarray(live.n, jnp.int32).reshape(1), decay, q, k, v, pool)


def lightning_step_auto(pool, layer, live, decay, q, k, v):
    """The kernel, through the Pallas interpreter off-TPU."""
    return lightning_step(pool, layer, live, decay, q, k, v,
                          interpret=jax.default_backend() != "tpu")
