"""Ring attention: causal attention with the sequence axis sharded over the
``sp`` mesh axis.

Long-context prefill where one chip cannot hold the whole [T, T] interaction:
each device keeps its local Q/K/V sequence chunk; K/V chunks rotate around
the ring via ``ppermute`` (one ICI hop per step) while each device folds the
incoming block into a running online-softmax state — compute and transfer
overlap, memory stays O(T/n per chip). The reference has no analog (context
length is whatever external llama.cpp supports — SURVEY.md §5 long-context);
this is the TPU-native design the KV layout [B, L, Hkv, S, D] was chosen for:
adding the sp axis shards S without relayout.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .mesh import AXIS_SP

_NEG_INF = -1e30

# serving gate: prompts below this many tokens prefill on the dense/flash
# path even when the mesh has an sp axis — ring rotation latency only pays
# for itself once the [T, T] interaction stops fitting one chip's lane
_RING_PREFILL_MIN_DEFAULT = 4096


def ring_prefill_min_tokens(default: int = _RING_PREFILL_MIN_DEFAULT) -> int:
    """Token threshold (env ``RING_PREFILL_MIN_TOKENS``) above which fresh
    prefill routes through :func:`ring_attention` on an sp>1 mesh. Read at
    trace time — each prefill bucket's program bakes its own decision, so
    one serving grid mixes dense short-prompt and ring long-prompt
    programs."""
    import os

    try:
        return int(os.environ.get("RING_PREFILL_MIN_TOKENS", default))
    except ValueError:
        return default


def use_ring_prefill(mesh: Mesh | None, t: int) -> bool:
    """Should a fresh prefill of ``t`` tokens take the ring path on this
    mesh? Requires an sp axis > 1, the threshold, and sp | t (shard_map
    needs equal sequence chunks)."""
    if mesh is None or t <= 1:
        return False
    sp = mesh.shape.get(AXIS_SP, 1) if AXIS_SP in mesh.axis_names else 1
    return sp > 1 and t >= ring_prefill_min_tokens() and t % sp == 0


def _block_attn(q, k, v, mask, scale):
    """One K/V block folded into online-softmax partials.

    q: [B, Tq, Hq, D]; k, v: [B, Tk, Hkv, D]; mask: [Tq, Tk] bool.
    Returns (acc [B, Hkv, G, Tq, D] f32 unnormalized, m, l [B, Hkv, G, Tq]).
    """
    b, tq, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, tq, hkv, g, d)
    s = jnp.einsum("bthgd,bshd->bhgts", qg, k, preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask[None, None, None, :, :], s, _NEG_INF)
    m = jnp.max(s, axis=-1)
    # explicit zero for masked entries: when a row is fully masked m == NEG_INF
    # and exp(s - m) would be exp(0) = 1 there
    p = jnp.where(mask[None, None, None, :, :], jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhgts,bshd->bhgtd", p, v.astype(jnp.float32))
    return acc, m, l


def _merge(acc1, m1, l1, acc2, m2, l2):
    m = jnp.maximum(m1, m2)
    c1 = jnp.exp(m1 - m)
    c2 = jnp.exp(m2 - m)
    return acc1 * c1[..., None] + acc2 * c2[..., None], m, l1 * c1 + l2 * c2


def ring_attention(
    q: jax.Array,  # [B, T, Hq, D] — T sharded on sp
    k: jax.Array,  # [B, T, Hkv, D]
    v: jax.Array,
    scale: float,
    mesh: Mesh,
    axis: str = AXIS_SP,
) -> jax.Array:
    """Causal attention with T sharded over ``axis``. Returns [B, T, Hq, D]
    in q.dtype, sharded like q."""
    n = mesh.shape[axis]
    perm = [(i, (i + 1) % n) for i in range(n)]

    def local(q, k, v):
        b, tq, hq, d = q.shape
        hkv = k.shape[2]
        g = hq // hkv
        idx = jax.lax.axis_index(axis)
        q_pos = idx * tq + jnp.arange(tq)

        def step(s, carry):
            acc, m, l, kc, vc = carry
            src = (idx - s) % n
            k_pos = src * tq + jnp.arange(tq)
            mask = k_pos[None, :] <= q_pos[:, None]
            acc_b, m_b, l_b = _block_attn(q, kc, vc, mask, scale)
            acc, m, l = _merge(acc, m, l, acc_b, m_b, l_b)
            kc = jax.lax.ppermute(kc, axis, perm)
            vc = jax.lax.ppermute(vc, axis, perm)
            return acc, m, l, kc, vc

        # mark the zero-init carry as device-varying over the ring axis so
        # the scan carry type matches its (varying) outputs. The marker has
        # moved across JAX versions (pcast -> pvary) and older releases
        # (<= 0.4.x) have neither — there the varying-axes type system does
        # not exist and the plain carry is already correct
        _pcast = getattr(jax.lax, "pcast", None)
        _pvary = getattr(jax.lax, "pvary", None)
        if _pcast is not None:
            vary = lambda x: _pcast(x, (axis,), to="varying")
        elif _pvary is not None:
            vary = lambda x: _pvary(x, (axis,))
        else:
            vary = lambda x: x
        acc0 = vary(jnp.zeros((b, hkv, g, tq, d), jnp.float32))
        m0 = vary(jnp.full((b, hkv, g, tq), _NEG_INF, jnp.float32))
        l0 = vary(jnp.zeros((b, hkv, g, tq), jnp.float32))
        acc, m, l, _, _ = jax.lax.fori_loop(0, n, step, (acc0, m0, l0, k, v))
        out = acc / jnp.maximum(l, 1e-30)[..., None]  # [B, Hkv, G, Tq, D]
        return out.transpose(0, 3, 1, 2, 4).reshape(b, tq, hq, d).astype(q.dtype)

    seq = P(None, axis, None, None)
    fn = shard_map(local, mesh=mesh, in_specs=(seq, seq, seq), out_specs=seq)
    return fn(q, k, v)


def ring_attention_sharded(q, k, v, scale: float, mesh: Mesh) -> jax.Array:
    """Convenience: sp ring when the mesh has an sp axis > 1, dense otherwise."""
    if AXIS_SP in mesh.axis_names and mesh.shape[AXIS_SP] > 1:
        return ring_attention(q, k, v, scale, mesh)
    from ..ops.layers import gqa_attention

    t = q.shape[1]
    pos = jnp.arange(t)
    mask = jnp.broadcast_to(pos[None, :] <= pos[:, None], (q.shape[0], t, t))
    return gqa_attention(q, k, v, mask, scale)
