"""Tensor-parallel collective/compute overlap for the decode layer stack.

Under GSPMD, the row-sharded output projections (attention ``wo`` and the
FFN ``w_down``) each end in one blocking all-reduce: the ICI sits idle
while the MXU computes the partial matmul, then the MXU sits idle while
the all-reduce moves d_model bytes — back-to-back, every layer, every
decode step. At decode batch sizes the matmuls are tiny, so the collective
is a large fixed fraction of step latency (the classic Megatron overlap
argument).

``TP_OVERLAP=1`` swaps that single psum for an explicit shard_map ring:
the all-reduce decomposes into 2(tp-1) ``ppermute`` hops over d_model/tp
chunks (reduce-scatter then all-gather), each hop's DMA independent of the
adds on the chunks already in flight — the XLA scheduler interleaves the
sends with the adjacent chunk's compute instead of serializing one bulk
collective after the whole matmul. Decomposed summation also changes the
reduction ORDER, so results differ from the psum path by float rounding
(greedy tokens stay stable in the equivalence tests); the knob therefore
defaults OFF and the GSPMD path stays the bit-reference.

The helpers accept plain arrays, int8 ``QTensor`` and grouped-int4
``QTensor4`` weights: shard_map sees the registered pytrees, so the
per-shard body reuses the exact same ``mm``/``swiglu`` kernels as the
GSPMD path on each shard's slice.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..ops.layers import swiglu
from ..ops.wquant import QTensor, QTensor4, mm
from .mesh import AXIS_TP

__all__ = [
    "tp_overlap_enabled",
    "ring_all_reduce",
    "overlap_row_proj",
    "overlap_ffn",
]


def tp_overlap_enabled() -> bool:
    """TP_OVERLAP=1 turns on the ppermute ring for decode projections."""
    return os.environ.get("TP_OVERLAP", "0").strip().lower() in ("1", "true", "on")


def _tp(mesh) -> int:
    if mesh is None:
        return 1
    return mesh.shape.get(AXIS_TP, 1)


def ring_all_reduce(y: jax.Array, axis_name: str, tp: int) -> jax.Array:
    """All-reduce ``y`` over ``axis_name`` as a reduce-scatter/all-gather
    ppermute ring (must run inside shard_map). The last axis splits into
    ``tp`` chunks; each of the 2(tp-1) hops moves one chunk while the adds
    on the previously-received chunk proceed. Falls back to psum when the
    last axis does not split."""
    if tp <= 1:
        return y
    d = y.shape[-1]
    if d % tp:
        return jax.lax.psum(y, axis_name)
    c = d // tp
    idx = jax.lax.axis_index(axis_name)
    fwd = [(j, (j + 1) % tp) for j in range(tp)]
    chunks = y.reshape(*y.shape[:-1], tp, c)
    ax = chunks.ndim - 2

    def chunk(j):
        return jax.lax.dynamic_index_in_dim(chunks, j % tp, axis=ax,
                                            keepdims=False)

    # reduce-scatter: start one chunk "ahead"; each hop delivers the
    # running partial for the chunk this shard adds its local copy to.
    # After tp-1 hops shard idx owns the FULL sum of chunk (idx+2) % tp.
    acc = chunk(idx + 1)
    for s in range(1, tp):
        acc = jax.lax.ppermute(acc, axis_name, fwd)
        acc = acc + chunk(idx + 1 - s)

    # all-gather: circulate the owned chunks back around the same ring
    out = jnp.zeros_like(chunks)

    def put(buf, j, val):
        return jax.lax.dynamic_update_index_in_dim(buf, val, j % tp, axis=ax)

    out = put(out, idx + 2, acc)
    cur = acc
    for h in range(1, tp):
        cur = jax.lax.ppermute(cur, axis_name, fwd)
        out = put(out, idx + 2 - h, cur)
    return out.reshape(y.shape)


def _weight_specs(w, row_sharded: bool):
    """The shard_map in_spec pytree for one projection weight.

    row_sharded: contraction axis on tp (wo / w_down — the overlap
    targets); else column-sharded (w_gate / w_up). int8 QTensor scales are
    extent-1 on the contraction axis, so they never shard on it; grouped
    QTensor4 scales/zeros shard exactly as the codes (see shard_params)."""
    wspec = P(AXIS_TP, None) if row_sharded else P(None, AXIS_TP)
    if isinstance(w, QTensor):
        return QTensor(q=wspec, s=P(None, None) if row_sharded else wspec)
    if isinstance(w, QTensor4):
        return QTensor4(q=wspec, s=wspec, z=wspec, group=w.group)
    return wspec


def overlap_row_proj(x: jax.Array, w, mesh) -> jax.Array:
    """``x @ w`` for a row-sharded (contraction on tp) projection with the
    trailing all-reduce done as the ppermute ring. ``x``'s last axis must
    carry the matching tp sharding (the attention heads fold) — the
    per-shard slice feeds the local matmul directly."""
    tp = _tp(mesh)
    if tp <= 1:
        return mm(x, w)
    xspec = P(*([None] * (x.ndim - 1) + [AXIS_TP]))

    def f(xs, ws):
        return ring_all_reduce(mm(xs, ws), AXIS_TP, tp)

    return shard_map(
        f, mesh=mesh,
        in_specs=(xspec, _weight_specs(w, row_sharded=True)),
        out_specs=P(*([None] * x.ndim)), check_vma=False,
    )(x, w)


def overlap_ffn(h: jax.Array, w_gate, w_up, w_down, act: str, mesh) -> jax.Array:
    """The whole SwiGLU FFN in one shard_map: gate/up column shards feed
    the row-sharded down projection without rematerializing the [.., ff]
    intermediate across shards, and the down matmul's all-reduce rides the
    ppermute ring. ``h`` is replicated (the layer input after the attention
    all-reduce)."""
    tp = _tp(mesh)
    if tp <= 1:
        return swiglu(h, w_gate, w_up, w_down, act)
    hspec = P(*([None] * h.ndim))

    def f(hs, wg, wu, wd):
        return ring_all_reduce(swiglu(hs, wg, wu, wd, act), AXIS_TP, tp)

    return shard_map(
        f, mesh=mesh,
        in_specs=(
            hspec,
            _weight_specs(w_gate, row_sharded=False),
            _weight_specs(w_up, row_sharded=False),
            _weight_specs(w_down, row_sharded=True),
        ),
        out_specs=hspec, check_vma=False,
    )(h, w_gate, w_up, w_down)
