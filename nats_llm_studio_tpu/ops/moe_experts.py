"""The routed experts without the terms that are zero: two kernels.

**Few rows: only the experts hit are read.** A decode step of 8 rows x top-4
picks at most 32 of a layer's 64 experts, and usually far fewer; the dense
dispatch (``models/mla_moe.py moe_ffn``) streams all 64. Here the experts
the live rows hit are listed on the device (``hit_list``) and
``moe_hit_experts`` streams the three matrices of those experts alone, each
once, every row computed on every listed expert.

**Many rows: only the picks are computed.** A prefill chunk of 256-1,024
rows hits every expert it is going to hit anyway; what the dense dispatch
wastes there is products, rows x 64 experts where rows x 4 were picked. The
(row, pick) pairs are sorted by expert (``sort_by_expert``) and
``moe_grouped_experts`` walks the sorted rows a tile at a time, each tile
once for every expert that has rows in it (``group_visits``): rows x 4
products plus the tiles' edges under any routing, an expert's matrices read
once a run of tiles and not at all where no row picked it.

Both take the WHOLE stacks ``[L, E, d, f]`` with (layer, expert) as indices:
a layer's slice handed over as an operand would be copied first (1.4 GB a
layer at the published widths).

An expert is three matrices, ``silu(x W_gate) * (x W_up)`` then ``W_down``, or
TWO where ``w_gate`` is None: ``relu(x W_up)^2`` then ``W_down`` (the
``nemotron_h`` experts; ``d`` is then whatever width they work at).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# what the three double-buffered weight tiles of a grid step may take of VMEM
# (at d 3,584 in bf16: 512 columns of f, 3.7 MB a tile; half that read 7 %
# slower on a v5e, twice that the same: PERF.md, PR 30)
_TILE_BYTES = 24 << 20
# sorted (row, pick) pairs a grid step of the grouped kernel computes (256
# read 8-10 % slower at 1,024 rows: a tile is computed whole for every expert
# with a row in it, and the tail experts have few), and the columns of f a
# trip of its inner loop takes (the kernel's program text is one trip's:
# unrolled over all 1,024 columns it compiled in 2.7 s for 0.85 and ran 3 %
# faster, and ~20 prefill programs of a cold run each compile it: PERF.md,
# PR 32)
_ROW_TILE = 128
_F_TRIP = 256


def hit_list(rows_on: jax.Array, places: int) -> tuple[jax.Array, jax.Array]:
    """(ids [places] int32, n_hit int32) from ``rows_on`` [E], the live rows
    on each expert: the experts with a row, in rising order, then the last of
    them again (a place past ``n_hit`` names nothing new to read)."""
    e = rows_on.shape[0]
    hit = rows_on > 0
    n_hit = jnp.sum(hit).astype(jnp.int32)
    place = jnp.cumsum(hit) - 1  # where a hit expert stands in the list
    at = (place[None, :] == jnp.arange(places)[:, None]) & hit[None, :]  # [places, E]
    ids = jnp.sum(at * jnp.arange(e)[None, :], axis=1)
    last = ids[jnp.maximum(n_hit - 1, 0)]
    return jnp.where(jnp.arange(places) < n_hit, ids, last).astype(jnp.int32), n_hit


def _f_tile(d: int, f: int, itemsize: int, matrices: int = 3) -> int:
    """Columns of ``f`` a grid step takes: the widest lane-aligned divisor of
    ``f`` whose tiles (one a matrix), double-buffered, stay under
    ``_TILE_BYTES``."""
    fits = [t for t in range(128, f + 1, 128)
            if f % t == 0 and 2 * matrices * d * t * itemsize <= _TILE_BYTES]
    return max(fits) if fits else f


def _activation(x, w_refs, at=slice(None)):
    """An expert's activation of rows ``x`` over the columns ``at`` of f, in
    float32: ``w_refs`` is (gate, up) or, without a gate matrix, (up,)."""
    dots = [jnp.dot(x, w[:, at], preferred_element_type=jnp.float32) for w in w_refs]
    if len(dots) == 1:
        return jnp.square(jnp.maximum(dots[0], 0.0))
    return jax.nn.silu(dots[0]) * dots[1]


def _hit_kernel(ids_ref, n_ref, layer_ref, h_ref, gate_ref, init_ref, *refs):
    """Grid (places, tiles of f): place i is expert ``ids[i]``; its rows are
    gated by ``gate[i]`` (0 for a row that did not pick it) and summed into
    the float32 output block, which stays in VMEM across the whole grid and
    starts from ``init`` (the shared expert's output). ``refs``: the expert's
    (gate,) up and down tiles, then the output."""
    *w_refs, wd_ref, o_ref = refs
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _start():
        o_ref[...] = init_ref[...]

    @pl.when(i < n_ref[0])
    def _expert():
        h = h_ref[...]
        act = (_activation(h, w_refs) * gate_ref[i]).astype(h.dtype)
        o_ref[...] += jnp.dot(act, wd_ref[...], preferred_element_type=jnp.float32)


def moe_hit_experts(
    h: jax.Array,       # [R, d] rows
    gates: jax.Array,   # [P, R] f32: gates[i, r] = row r's gate on expert ids[i]
    ids: jax.Array,     # [P] int32 (hit_list)
    n_hit: jax.Array,   # int32 scalar
    layer,              # int32 scalar: the layer's place in the stacks
    w_gate: jax.Array | None,  # [L, E, d, f]; None: two-matrix relu^2 experts
    w_up: jax.Array,    # [L, E, d, f]
    w_down: jax.Array,  # [L, E, f, d]
    init: jax.Array,    # [R, d] f32
    interpret: bool = False,
) -> jax.Array:
    """init + sum over the listed experts of gate x expert(rows): [R, d] f32."""
    r, d = h.shape
    p = ids.shape[0]
    f = w_up.shape[-1]
    ups = (w_up,) if w_gate is None else (w_gate, w_up)
    isz = w_up.dtype.itemsize
    tf = _f_tile(d, f, isz, len(ups) + 1)
    nt = f // tf
    mult = 8 if h.dtype.itemsize >= 4 else 16
    rp = -(-r // mult) * mult
    if rp != r:
        h = jnp.pad(h, ((0, rp - r), (0, 0)))
        init = jnp.pad(init, ((0, rp - r), (0, 0)))
        gates = jnp.pad(gates, ((0, 0), (0, rp - r)))

    def tile(i, j, n_ref):  # a place past the list stays on the last tile read
        return jnp.where(i < n_ref[0], j, nt - 1)

    def whole(i, j, ids_ref, n_ref, layer_ref):
        return (0, 0)

    def up_map(i, j, ids_ref, n_ref, layer_ref):
        return (layer_ref[0], ids_ref[i], 0, tile(i, j, n_ref))

    def down_map(i, j, ids_ref, n_ref, layer_ref):
        return (layer_ref[0], ids_ref[i], tile(i, j, n_ref), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(p, nt),
        in_specs=[pl.BlockSpec((rp, d), whole),
                  pl.BlockSpec((p, rp, 1), lambda i, j, *_: (0, 0, 0)),
                  pl.BlockSpec((rp, d), whole)]
                 + [pl.BlockSpec((None, None, d, tf), up_map)] * len(ups)
                 + [pl.BlockSpec((None, None, tf, d), down_map)],
        out_specs=pl.BlockSpec((rp, d), whole),
    )
    out = pl.pallas_call(
        _hit_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rp, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=2 * (len(ups) + 1) * d * tf * isz + (16 << 20)),
        interpret=interpret,
        # a constant: the custom call's name in a device trace
        name="moe_hit_experts",
    )(
        ids.astype(jnp.int32), jnp.asarray(n_hit, jnp.int32).reshape(1),
        jnp.asarray(layer, jnp.int32).reshape(1),
        h, gates.astype(jnp.float32)[..., None], init.astype(jnp.float32),
        *ups, w_down,
    )
    return out[:r]


def moe_hit_experts_auto(h, gates, ids, n_hit, layer, w_gate, w_up, w_down, init):
    """The kernel, through the Pallas interpreter off-TPU."""
    return moe_hit_experts(h, gates, ids, n_hit, layer, w_gate, w_up, w_down, init,
                           interpret=jax.default_backend() != "tpu")


def sort_by_expert(idx: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(order, place) for picks ``idx`` [rows, k]: ``order`` [rows x k] lists
    the (row, pick) pairs (pair = row x k + pick) by rising expert, a stable
    sort; ``place`` [rows, k] is where each pair stands in that order."""
    order = jnp.argsort(idx.reshape(-1), stable=True)
    return order.astype(jnp.int32), jnp.argsort(order).astype(jnp.int32).reshape(idx.shape)


def group_visits(sizes: jax.Array, pairs: int, tm: int):
    """The grouped kernel's walk over ``pairs`` sorted rows in tiles of
    ``tm``, from ``sizes`` [E], the rows of each expert: (expert [V], tile
    [V], n) for V = tiles + E - 1 places of which the first ``n`` are real,
    and (starts, ends) [E], each expert's rows. A tile is visited once for
    every expert with a row in it, experts rising, tiles rising: an expert's
    visits are consecutive, and so are a tile's."""
    e = sizes.shape[0]
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    n_of = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    upto = jnp.cumsum(n_of)
    v = jnp.arange(-(-pairs // tm) + e - 1, dtype=jnp.int32)
    expert = jnp.minimum(jnp.sum(v[:, None] >= upto[None, :], axis=1), e - 1)
    tile = first[expert] + v - (upto - n_of)[expert]
    return (expert.astype(jnp.int32), tile.astype(jnp.int32), upto[-1].astype(jnp.int32),
            starts, ends)


def _grouped_kernel(tm, fc, expert_ref, tile_ref, starts_ref, ends_ref, layer_ref,
                    x_ref, gate_ref, *refs):
    """Grid (visits,): visit i is tile ``tile[i]`` of the sorted rows on
    expert ``expert[i]``, whose matrices ((gate,) up, down) are the step's
    blocks WHOLE (a
    run of visits on one expert fetches them once). The whole tile is
    computed, ``fc`` columns of f a trip of one loop; only the rows of that
    expert are kept, the rest of the output block staying what the tile's
    earlier visits made it (it is held in VMEM until the tile changes)."""
    *w_refs, wd_ref, o_ref, acc_ref = refs
    i = pl.program_id(0)
    x = x_ref[...]
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def columns(c, carry):
        at = pl.ds(pl.multiple_of(c * fc, fc), fc)
        act = _activation(x, w_refs, at).astype(x.dtype)
        acc_ref[...] += jnp.dot(act, wd_ref[at, :], preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, wd_ref.shape[0] // fc, columns, 0)
    row = tile_ref[i] * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    mine = (row >= starts_ref[expert_ref[i]]) & (row < ends_ref[expert_ref[i]])
    o_ref[...] = jnp.where(mine, acc_ref[...] * gate_ref[...], o_ref[...])


def moe_grouped_experts(
    x: jax.Array,       # [P, d]: the pairs' rows, sorted by expert
    gate: jax.Array,    # [P] f32: each pair's gate
    sizes: jax.Array,   # [E] int: rows of each expert (they sum to the real pairs)
    layer,              # int32 scalar: the layer's place in the stacks
    w_gate: jax.Array | None,  # [L, E, d, f]; None: two-matrix relu^2 experts
    w_up: jax.Array,    # [L, E, d, f]
    w_down: jax.Array,  # [L, E, f, d]
    interpret: bool = False,
) -> jax.Array:
    """gate x each sorted row's own expert of it: [P, d] f32. An
    expert's matrices, double-buffered, have to fit VMEM beside the row
    tiles (44 of 128 MB for three of [3,584, 1,024] in bf16)."""
    p, d = x.shape
    f = w_up.shape[-1]
    ups = (w_up,) if w_gate is None else (w_gate, w_up)
    isz = w_up.dtype.itemsize
    mult = 8 if x.dtype.itemsize >= 4 else 16
    tm = min(_ROW_TILE, -(-p // mult) * mult)
    pp = -(-p // tm) * tm
    if pp != p:  # rows past the last expert's: in no group, kept by no visit
        x = jnp.pad(x, ((0, pp - p), (0, 0)))
        gate = jnp.pad(gate, (0, pp - p))
    expert, tile, n, starts, ends = group_visits(sizes, pp, tm)

    def rows_map(i, expert_ref, tile_ref, *_):
        return (tile_ref[i], 0)

    def expert_map(i, expert_ref, tile_ref, starts_ref, ends_ref, layer_ref):
        return (layer_ref[0], expert_ref[i], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n,),
        in_specs=[pl.BlockSpec((tm, d), rows_map),
                  pl.BlockSpec((tm, 1), rows_map)]
                 + [pl.BlockSpec((None, None, d, f), expert_map)] * len(ups)
                 + [pl.BlockSpec((None, None, f, d), expert_map)],
        out_specs=pl.BlockSpec((tm, d), rows_map),
        scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)],
    )
    fc = math.gcd(f, _F_TRIP)
    out = pl.pallas_call(
        functools.partial(_grouped_kernel, tm, fc),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((pp, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=(2 * (len(ups) + 1) * d * f * isz
                              + tm * d * (2 * x.dtype.itemsize + 12)
                              + 4 * tm * fc * 4 + (8 << 20))),
        interpret=interpret,
        # a constant: the custom call's name in a device trace
        name="moe_grouped_experts",
    )(
        expert, tile, starts, ends, jnp.asarray(layer, jnp.int32).reshape(1),
        x, gate.astype(jnp.float32)[:, None], *ups, w_down,
    )
    return out[:p]


def moe_grouped_experts_auto(x, gate, sizes, layer, w_gate, w_up, w_down):
    """The kernel, through the Pallas interpreter off-TPU."""
    return moe_grouped_experts(x, gate, sizes, layer, w_gate, w_up, w_down,
                               interpret=jax.default_backend() != "tpu")
