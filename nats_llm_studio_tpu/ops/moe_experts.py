"""The routed experts of a step with few rows: only the experts hit are read.

A decode step of 8 rows x top-4 picks at most 32 of a layer's 64 experts,
and usually far fewer; the dense dispatch (``models/mla_moe.py moe_ffn``)
streams all 64. Here the experts the live rows hit are listed on the device
(``hit_list``) and ``moe_hit_experts`` streams the three matrices of those
experts alone, each once, out of the WHOLE stacks ``[L, E, d, f]`` with
(layer, expert) as indices: a layer's slice handed over as an operand would
be copied first (1.4 GB a layer at the published widths).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# what the three double-buffered weight tiles of a grid step may take of VMEM
# (at d 3,584 in bf16: 512 columns of f, 3.7 MB a tile; half that read 7 %
# slower on a v5e, twice that the same: PERF.md, PR 30)
_TILE_BYTES = 24 << 20


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


def _f_tile(d: int, f: int, itemsize: int) -> int:
    """Columns of ``f`` a grid step takes: the widest lane-aligned divisor of
    ``f`` whose three tiles, double-buffered, stay under ``_TILE_BYTES``."""
    fits = [t for t in range(128, f + 1, 128)
            if f % t == 0 and 6 * d * t * itemsize <= _TILE_BYTES]
    return max(fits) if fits else f


def _hit_kernel(ids_ref, n_ref, layer_ref, h_ref, gate_ref, init_ref,
                wg_ref, wu_ref, wd_ref, o_ref):
    """Grid (places, tiles of f): place i is expert ``ids[i]``; its rows are
    gated by ``gate[i]`` (0 for a row that did not pick it) and summed into
    the float32 output block, which stays in VMEM across the whole grid and
    starts from ``init`` (the shared expert's output)."""
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _start():
        o_ref[...] = init_ref[...]

    @pl.when(i < n_ref[0])
    def _expert():
        h = h_ref[...]
        g = jnp.dot(h, wg_ref[...], preferred_element_type=jnp.float32)
        u = jnp.dot(h, wu_ref[...], preferred_element_type=jnp.float32)
        act = (jax.nn.silu(g) * u * gate_ref[i]).astype(h.dtype)
        o_ref[...] += jnp.dot(act, wd_ref[...], preferred_element_type=jnp.float32)


def moe_hit_experts(
    h: jax.Array,       # [R, d] rows
    gates: jax.Array,   # [P, R] f32: gates[i, r] = row r's gate on expert ids[i]
    ids: jax.Array,     # [P] int32 (hit_list)
    n_hit: jax.Array,   # int32 scalar
    layer,              # int32 scalar: the layer's place in the stacks
    w_gate: jax.Array,  # [L, E, d, f]
    w_up: jax.Array,    # [L, E, d, f]
    w_down: jax.Array,  # [L, E, f, d]
    init: jax.Array,    # [R, d] f32
    interpret: bool = False,
) -> jax.Array:
    """init + sum over the listed experts of gate x SwiGLU(rows): [R, d] f32."""
    r, d = h.shape
    p = ids.shape[0]
    f = w_gate.shape[-1]
    tf = _f_tile(d, f, w_gate.dtype.itemsize)
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
                  pl.BlockSpec((rp, d), whole),
                  pl.BlockSpec((None, None, d, tf), up_map),
                  pl.BlockSpec((None, None, d, tf), up_map),
                  pl.BlockSpec((None, None, tf, d), down_map)],
        out_specs=pl.BlockSpec((rp, d), whole),
    )
    out = pl.pallas_call(
        _hit_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rp, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=6 * d * tf * w_gate.dtype.itemsize + (16 << 20)),
        interpret=interpret,
        # a constant: the custom call's name in a device trace
        name="moe_hit_experts",
    )(
        ids.astype(jnp.int32), jnp.asarray(n_hit, jnp.int32).reshape(1),
        jnp.asarray(layer, jnp.int32).reshape(1),
        h, gates.astype(jnp.float32)[..., None], init.astype(jnp.float32),
        w_gate, w_up, w_down,
    )
    return out[:r]


def moe_hit_experts_auto(h, gates, ids, n_hit, layer, w_gate, w_up, w_down, init):
    """The kernel, through the Pallas interpreter off-TPU."""
    return moe_hit_experts(h, gates, ids, n_hit, layer, w_gate, w_up, w_down, init,
                           interpret=jax.default_backend() != "tpu")
