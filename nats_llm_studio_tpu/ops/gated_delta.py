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

**Decode** is two Pallas calls a layer a step, between the in-projections
and the out-projection, which stay XLA's. ``step_inputs`` (``gdn_step_inputs``
in a device trace) takes the products' raw output and the layer's convolution
tail (a tap a plane) with the slots on the sublanes and does, a head's
channels at a time, what
lies before the rule: the shift of a ``fresh`` row, the taps' sum in float32,
SiLU, the cast where ``ssm_scan.conv_step`` casts, the L2 norm of q and k (q
times d_k^-0.5), v, beta = sigmoid(b) and alpha = exp(-exp(A_log) softplus(a +
dt_bias)); a live row that is not ``fresh`` keeps its tail and gets alpha 1,
beta 0. It writes them as the state kernel reads them. ``gated_delta_step`` is
one kernel over the state pool ``[slots, layers, H_v, d_k, d_v]``, aliased onto
its output, that moves only the slots that hold a request (``ssm_scan.
LiveSlots``: grid place g is slot ``order[g]``, a place past the list names the
block before it again, which is neither fetched nor written twice). The minor
plane is [d_k, d_v] with d_v on the lanes: a block's KEY heads come in as rows
[heads, d_k], once for the value heads each serves, and are transposed to
columns in VMEM; v rides in as lane rows, alpha and beta as scalars (SMEM), so
the two reads are sums over sublanes and the write a broadcast multiply-add.
The read-outs S^T q stay in VMEM; the grid's last cell normalises them a head
(the gated norm), multiplies by the gain and SiLU(z), z read from the
in-projection's own output, and writes the [slots, H_v d_v] rows ``w_out``
reads, zeros for a slot that is not live. The layers' small leaves ride in as
whole stacks (``step_consts``, once a step) and a kernel takes its layer's row
by the prefetched layer. ``gated_delta_step_xla`` is the state kernel in plain
XLA and ``models/gdn_moe.py linear_step_xla`` the whole step as the equations
are written: what the kernels are held to, run by no program.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ssm_scan import LiveSlots, _heads_block

_HI = jax.lax.Precision.HIGHEST
# the chunked rule's tile: tokens solved together (one [C, C] unit-triangular
# system a chunk and head); a size of the computation, not of a model
CHUNK = 64
_L2_EPS = 1e-6
# slots a grid cell of ``step_inputs`` takes, on the sublanes (one bf16 tile's rows),
# and the heads one turn of its loop takes
_ROWS = 16
_UNROLL = 4


def l2_normalise(x: jax.Array, eps: float = _L2_EPS) -> jax.Array:
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


class StepConsts(NamedTuple):
    """What the linear layers of ONE decode step share, made once a step
    (``step_consts``), so that no layer slices or converts a small leaf: the
    whole stacks in the kernels' layouts (a kernel takes its layer's row by
    the prefetched ``layer``) and the step's ``fresh`` flags as a column."""
    conv_w: jax.Array   # [Ll, K, C] f32
    a_log: jax.Array    # [Ll, 1, H] f32
    dt_bias: jax.Array  # [Ll, 1, H] f32
    gain: jax.Array     # [Ll, 1, d_v] f32: the gated norm's
    eps: jax.Array      # [1] f32: the gated norm's
    fresh: jax.Array    # [slots, 1] int32: live rows that consume their position


def step_consts(lin, eps: float, fresh: jax.Array) -> StepConsts:
    """``lin``: the stacked leaves of the linear layers (``conv_w`` [Ll, K, C],
    ``a_log``, ``dt_bias`` [Ll, H], ``gate_norm`` [Ll, d_v]); ``fresh`` [slots] bool."""
    f32 = jnp.float32
    return StepConsts(lin["conv_w"].astype(f32), lin["a_log"].astype(f32)[:, None],
                      lin["dt_bias"].astype(f32)[:, None], lin["gate_norm"].astype(f32)[:, None],
                      jnp.full((1,), eps, f32), fresh.astype(jnp.int32)[:, None])


class Values(NamedTuple):
    """The value side of a step: what it writes, and the gate its read-out
    leaves the kernel through. One operand, so that the step keeps the eight
    it always had (pool, layer, live, decay, beta, q, k, v): the faults that
    tests and the benchmark's rehearsal put into a decode step wrap
    ``gated_delta_step_auto`` by that signature and change ``beta`` alone."""
    v: jax.Array     # [slots, H, d_v] f32
    zs: jax.Array    # [slots, .. | H x d_v]: the in-projections' raw output, z its LAST columns
    gain: jax.Array  # ``StepConsts.gain``
    eps: jax.Array   # ``StepConsts.eps``


def gated_norm(o: jax.Array, z: jax.Array, gain: jax.Array, eps) -> jax.Array:
    """o [.., H, d_v] f32 normalised a head, times the gain and SiLU(z), as
    [.., H x d_v] in z's dtype (what ``w_out`` reads)."""
    y = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * gain.astype(jnp.float32)
    y = y * jax.nn.silu(z.astype(jnp.float32).reshape(y.shape))
    return y.reshape(y.shape[:-2] + (-1,)).astype(z.dtype)


def _inputs_kernel(hk, dk, hv, dv, layer_ref, x_ref, ba_ref, fresh_ref, w_ref, a_ref, dt_ref,
                   tail_ref, tail_out, q_ref, k_ref, v_ref, decay_ref, beta_ref):
    """Grid (blocks of slots), the slots on the sublanes. x: the block's raw
    [q | k | v] channels [rows, C]; tail: its [K, rows, C] of the layer, a tap
    a plane; w [K, C]. One head's channels at a time, in a loop of ``_UNROLL``
    heads a turn. A head a turn the loop is a chain of latencies (a third of
    the step's gain, measured); every head the loop unrolls is traced again
    for every decode program a process builds (eight a turn serve no faster
    than four and add 2.5 s to a warm start; all 64 made a program compile in
    37 s for 8): the shift, the taps' sum,
    SiLU and the cast, then the head's L2 norm (q, k) or the plain rows (v).
    The gates of the block follow."""
    del layer_ref
    f32 = jnp.float32
    on = fresh_ref[...] > 0  # [rows, 1]
    taps = w_ref.shape[0]

    def conv(at, width):
        """The step of ``width`` channels from ``at``: [rows, width] f32."""
        cols = pl.ds(at, width)
        acc = None
        for j in range(taps):
            nxt = tail_ref[j + 1, :, cols] if j + 1 < taps else x_ref[:, cols]
            row = jnp.where(on, nxt.astype(f32), tail_ref[j, :, cols].astype(f32))
            tail_out[j, :, cols] = row.astype(tail_out.dtype)
            acc = w_ref[j:j + 1, cols] * row if acc is None else acc + w_ref[j:j + 1, cols] * row
        return jax.nn.silu(acc).astype(x_ref.dtype).astype(f32)  # rounded where ``conv_step`` rounds

    def heads(out_ref, first, width, scale):
        def one(h):
            y = conv(pl.multiple_of(first + h * width, width), width)
            if scale is not None:  # q and k: the head's L2 norm
                y = y * jax.lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True) + _L2_EPS) * scale
            out_ref[:, h, :] = y

        n = out_ref.shape[1]
        group = next(u for u in (_UNROLL, 4, 2, 1) if n % u == 0)

        def some(g, _):
            for u in range(group):
                one(g * group + u)
        jax.lax.fori_loop(0, n // group, some, None)

    heads(q_ref, 0, dk, dk**-0.5)
    heads(k_ref, hk * dk, dk, 1.0)
    heads(v_ref, 2 * hk * dk, dv, None)
    ba = ba_ref[...].astype(f32)
    dt = jax.nn.softplus(ba[:, hv:] + dt_ref[...])
    decay_ref[...] = jnp.where(on, jnp.exp(-jnp.exp(a_ref[...]) * dt), 1.0)
    beta_ref[...] = jnp.where(on, jax.nn.sigmoid(ba[:, :hv]), 0.0)


def step_inputs(qkvz: jax.Array, ba: jax.Array, tail: jax.Array, layer, consts: StepConsts,
                heads: tuple[int, int, int, int], interpret: bool = False):
    """Everything between a linear layer's in-projections and its state
    kernel, for ONE position of every slot, in one Pallas call: ``qkvz``
    [slots, C + H d_v] and ``ba`` [slots, 2 H] are the products' raw output,
    ``tail`` [K, slots, C] the layer's convolution tails, a tap a plane,
    written in place (aliased onto the result). The tails of ALL layers
    cannot ride in whole as the state pool does: XLA:TPU moves a custom call's
    19 MB operand into
    fast memory and back around every call (``tests/test_tpu_compile.py``
    reads the copies); the caller's slice and its write-back move the layer's
    2 MB once each. ``heads`` = (H_k, d_k, H, d_v). A ``fresh`` row shifts its
    input in, the others keep their tail bit for bit and come out with decay
    1, beta 0. Returns (tail, q [slots, H_k, d_k] f32 normalised and scaled,
    k likewise, v [slots, H, d_v] f32, decay [slots, H] f32, beta [slots, H]
    f32): the operands of ``gated_delta_step`` as that kernel reads them."""
    hk, dk, hv, dv = heads
    slots, c = qkvz.shape[0], 2 * hk * dk + hv * dv
    taps = tail.shape[0]
    rows = _ROWS if slots % _ROWS == 0 else slots
    f32 = jnp.float32

    def layer_row(i, layer_ref):
        return (layer_ref[0], 0, 0)

    block = lambda *shape: pl.BlockSpec(shape, lambda i, layer_ref: (i,) + (0,) * (len(shape) - 1))  # noqa: E731
    tails = pl.BlockSpec((taps, rows, c), lambda i, layer_ref: (0, i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(slots // rows,),
        in_specs=[block(rows, c), block(rows, 2 * hv), block(rows, 1),
                  pl.BlockSpec((None, taps, c), layer_row),
                  pl.BlockSpec((None, 1, hv), layer_row), pl.BlockSpec((None, 1, hv), layer_row),
                  tails],
        out_specs=[tails, block(rows, hk, dk), block(rows, hk, dk), block(rows, hv, dv),
                   block(rows, hv), block(rows, hv)],
    )
    return pl.pallas_call(
        lambda *refs: _inputs_kernel(hk, dk, hv, dv, *refs),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(tail.shape, tail.dtype),
                   jax.ShapeDtypeStruct((slots, hk, dk), f32),
                   jax.ShapeDtypeStruct((slots, hk, dk), f32),
                   jax.ShapeDtypeStruct((slots, hv, dv), f32),
                   jax.ShapeDtypeStruct((slots, hv), f32), jax.ShapeDtypeStruct((slots, hv), f32)],
        # operand 7 (after the prefetched layer) is the tail; result 0 is it again
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        # a constant: the custom call's name in a device trace. It must not hold
        # the state kernel's name: the benchmark reads that one by substring
        name="gdn_step_inputs",
    )(jnp.asarray(layer, jnp.int32).reshape(1), qkvz, ba, consts.fresh, consts.conv_w,
      consts.a_log, consts.dt_bias, tail)


def step_inputs_auto(qkvz, ba, tail, layer, consts, heads):
    """The kernel, through the Pallas interpreter off-TPU."""
    return step_inputs(qkvz, ba, tail, layer, consts, heads,
                       interpret=jax.default_backend() != "tpu")


def gated_delta_step_xla(pool, layer, live: LiveSlots, decay, beta, q, k, v: Values):
    """``gated_delta_step`` in plain XLA over every slot (what the kernel is
    held to): a slot that is not live keeps its state and reads zeros."""
    rep = pool.shape[2] // q.shape[1]
    s = jax.lax.dynamic_index_in_dim(pool, layer, axis=1, keepdims=False)
    o, s1 = gated_delta_recurrent(
        jnp.repeat(q, rep, axis=1)[:, None], jnp.repeat(k, rep, axis=1)[:, None], v.v[:, None],
        jnp.log(decay)[:, None], beta[:, None], s)
    on = live.mask[:, None, None, None]
    pool = jax.lax.dynamic_update_index_in_dim(pool, jnp.where(on, s1, s), layer, axis=1)
    gain = jax.lax.dynamic_index_in_dim(v.gain, layer, axis=0, keepdims=False)
    y = gated_norm(o[:, 0], v.zs[:, v.zs.shape[1] - o.shape[2] * o.shape[3]:], gain, v.eps[0])
    return pool, jnp.where(live.mask[:, None], y, jnp.zeros((), y.dtype))


def _step_kernel(hb, rep, layer_ref, order_ref, n_ref, decay_ref, beta_ref, eps_ref, q_ref, k_ref,
                 v_ref, z_ref, gain_ref, s_ref, so_ref, y_ref, kq_ref, o_ref):
    """Grid (places, blocks of heads): place g is slot ``order[g]``. decay,
    beta: [slots, H] scalars; q, k: the block's KEY heads [hb / rep, d_k] (a
    key head serves ``rep`` value heads, side by side); v: [hb, d_v] rows; s:
    the block [hb, d_k, d_v] of the slot's state in this layer. A place past
    ``n`` does nothing: its blocks are the ones the place before it named,
    still in VMEM and written back once. The read-outs S^T q wait in ``o``
    [blocks, slots to a whole 8, hb x d_v] (zeros at the start), the slots on
    the sublanes; the grid's last cell normalises them a head, gates them and
    writes y [slots, H x d_v] whole. A slot no place named reads zeros
    whatever its row of z holds: so does any head whose read-out is all
    zeros, which is what its norm gives anyway."""
    del layer_ref
    g, jj, n = pl.program_id(0), pl.program_id(1), n_ref[0]
    hk, lanes, slots = hb // rep, s_ref.shape[-1], y_ref.shape[0]

    @pl.when((g == 0) & (jj == 0))
    def _first():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(g < n)
    def _live():
        slot = order_ref[g]
        # k and q as COLUMNS, the block's key heads side by side on the lanes
        kq_ref[:, :hk] = k_ref[...].T
        kq_ref[:, hk:] = q_ref[...].T
        # a row is stored through its tile of 8: one row at a traced offset is not
        tile = pl.ds(pl.multiple_of(slot // 8 * 8, 8), 8)
        mine = jax.lax.broadcasted_iota(jnp.int32, (8, lanes), 0) == slot % 8
        for j in range(hb):
            h, at = jj * hb + j, slice(j * lanes, (j + 1) * lanes)
            kcol = kq_ref[:, j // rep:j // rep + 1]
            qcol = kq_ref[:, hk + j // rep:hk + j // rep + 1]
            s = s_ref[j] * decay_ref[slot, h]
            u = beta_ref[slot, h] * (v_ref[j:j + 1, :] - jnp.sum(s * kcol, axis=0, keepdims=True))
            s = s + kcol * u
            so_ref[j] = s
            o = jnp.sum(s * qcol, axis=0, keepdims=True)
            o_ref[jj, tile, at] = jnp.where(mine, o, o_ref[jj, tile, at])

    @pl.when((n == 0) & (g == 0) & (jj == 0))
    def _nothing_listed():
        # every place names ONE block, and the grid's end writes it back:
        # from what was read, not from a buffer nothing wrote
        so_ref[...] = s_ref[...]

    @pl.when((g == pl.num_programs(0) - 1) & (jj == pl.num_programs(1) - 1))
    def _read_out():
        gain, eps = gain_ref[...], eps_ref[0]
        for h in range(o_ref.shape[0] * hb):
            o = o_ref[h // hb, :slots, (h % hb) * lanes:(h % hb + 1) * lanes]
            ms = jnp.mean(o * o, axis=-1, keepdims=True)
            y = o * jax.lax.rsqrt(ms + eps) * gain
            y = y * jax.nn.silu(z_ref[:, h * lanes:(h + 1) * lanes].astype(jnp.float32))
            y_ref[:, h * lanes:(h + 1) * lanes] = jnp.where(ms > 0, y, 0.0).astype(y_ref.dtype)


def gated_delta_step(pool: jax.Array, layer, live: LiveSlots, decay: jax.Array,
                     beta: jax.Array, q: jax.Array, k: jax.Array, v: Values,
                     interpret: bool = False):
    """One position of the ``live`` slots in layer ``layer`` of the state pool
    ``[slots, L, H, d_k, d_v]`` f32, in place (the pool is aliased onto the
    result: donate it), from ``step_inputs``' results as they are: ``decay``
    [slots, H] = alpha (1 for a live row that must keep its state), ``beta``
    [slots, H] (0 likewise), ``q``, ``k`` [slots, H_k, d_k] (a key head is
    read for the H / H_k value heads it serves: nothing is repeated), ``v``
    the value rows and the read-out's gate (``Values``). Returns (pool, y
    [slots, H x d_v] in z's dtype = gated_norm(S^T q after the write) as
    ``w_out`` reads it; zeros for a slot that is not live, whose state is not
    touched)."""
    slots, _, h, dk, lanes = pool.shape
    hk = q.shape[1]
    rep = h // hk
    hbk = _heads_block(hk, dk, lanes * rep)  # key heads a block: rep x as many value heads
    hb, nj = hbk * rep, hk // hbk
    vd = h * lanes
    zs = v.zs
    if (zs.shape[1] - vd) % vd:  # z lies on no block of its own width: a copy (toy shapes)
        zs = zs[:, zs.shape[1] - vd:]
    z_at = zs.shape[1] // vd - 1

    def at(g, j, order_ref, n_ref):  # a place past the list stays on the last block
        return order_ref[g], jnp.where(g < n_ref[0], j, nj - 1)

    def heads_map(g, j, layer_ref, order_ref, n_ref):
        slot, j = at(g, j, order_ref, n_ref)
        return (slot, j, 0)

    def state_map(g, j, layer_ref, order_ref, n_ref):
        slot, j = at(g, j, order_ref, n_ref)
        return (slot, layer_ref[0], j, 0, 0)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    keys = pl.BlockSpec((None, hbk, dk), heads_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(slots, nj),
        in_specs=[smem, smem, smem, keys, keys,
                  pl.BlockSpec((None, hb, lanes), heads_map),
                  pl.BlockSpec((slots, vd), lambda g, j, *_: (0, z_at)),
                  pl.BlockSpec((None, 1, lanes), lambda g, j, layer_ref, *_: (layer_ref[0], 0, 0)),
                  pl.BlockSpec((None, None, hb, dk, lanes), state_map)],
        out_specs=[pl.BlockSpec((None, None, hb, dk, lanes), state_map),
                   pl.BlockSpec((slots, vd), lambda g, j, *_: (0, 0))],
        scratch_shapes=[pltpu.VMEM((dk, 2 * hbk), jnp.float32),
                        pltpu.VMEM((nj, -(-slots // 8) * 8, hb * lanes), jnp.float32)],
    )
    return pl.pallas_call(
        lambda *refs: _step_kernel(hb, rep, *refs),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((slots, vd), zs.dtype)],
        # operand 11 (after the prefetched layer and list) is the pool; result
        # 0 is it again
        input_output_aliases={11: 0},
        # a block is revisited along both axes: neither may be split
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        # a constant: the custom call's name in a device trace
        name="gated_delta_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), live.order,
      jnp.asarray(live.n, jnp.int32).reshape(1), decay, beta, v.eps, q, k, v.v, zs, v.gain, pool)


def gated_delta_step_auto(pool, layer, live, decay, beta, q, k, v):
    """The kernel, through the Pallas interpreter off-TPU."""
    return gated_delta_step(pool, layer, live, decay, beta, q, k, v,
                            interpret=jax.default_backend() != "tpu")
