"""Routed (sparse) MoE dispatch — the expert-parallel path.

The dense-dispatch form in models/llama.py computes every expert for every
token (E/k x wasted FLOPs — Mixtral top-2-of-8 does 4x extra work,
VERDICT.md missing #3). This module routes instead: each token's hidden
state is scattered into per-expert slot buffers of *static* capacity, each
expert runs one batched SwiGLU over its slots, and results gather back with
the routing weights. All shapes are static (XLA-friendly); token->slot
movement is scatter/gather (O(N*k*D)), not the one-hot-matmul dispatch whose
FLOPs explode at prefill token counts.

Expert parallelism (SURVEY.md §7 hard part #4) is a true ALL-TO-ALL over
the mesh's ``ep`` axis: tokens are sharded on ep, each shard routes its
N/ep tokens locally, exchanges only the assigned slot payloads
(``lax.all_to_all`` of [ep, E_local*C_pair, D] — per-shard bytes scale
with cf*k*N/ep*D, NOT with N*D like a replicate+psum), computes its own
E/ep experts over slots from every source, and a second all_to_all returns
the outputs for a local weighted combine. Expert weights arrive
pre-sharded on ep by ``sharding.param_sharding_rules``.

Capacity: per (source shard, expert) pair C_pair = ceil(cf * k * (N/ep)/E)
slots (total per-expert capacity ep*C_pair). Tokens overflowing their
pair's slots drop that expert's contribution (standard capacity-factor
semantics; the routing weight mass is not renormalized). cf defaults high
enough that drops require pathological routing skew.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..models.config import ModelConfig
from ..ops.wquant import q_einsum
from .mesh import AXIS_EP


def _capacity(n_tokens: int, cfg: ModelConfig, capacity_factor: float) -> int:
    per_expert = capacity_factor * cfg.n_experts_used * n_tokens / cfg.n_experts
    return max(1, math.ceil(per_expert))


def _route(xf: jax.Array, router, cfg: ModelConfig, capacity: int):
    """Top-k routing + slot assignment over the tokens GIVEN (the whole
    batch on a single shard; one shard's local block under EP — inside a
    shard the id g*C + pos is exactly the local all-to-all send-buffer
    layout dst*(E_local*C) + le*C + pos). Returns (top_w [N,k] f32,
    slot [N,k] int32 — slot id e*C + position, or the trash slot E*C for
    capacity overflow)."""
    n = xf.shape[0]
    e, k = cfg.n_experts, cfg.n_experts_used
    router_logits = q_einsum("nd,df->nf", xf, router).astype(jnp.float32)
    top_w, top_idx = jax.lax.top_k(router_logits, k)  # [N,k]
    top_w = jax.nn.softmax(top_w, axis=-1)

    # position of assignment (n, j) within its expert, in (n-major, j-minor)
    # order: running count of prior assignments to the same expert
    flat_e = top_idx.reshape(-1)  # [N*k]
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)  # [N*k, E]
    pos = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1  # [N*k]
    slot = jnp.where(pos < capacity, flat_e * capacity + pos, e * capacity)
    return top_w, slot.reshape(n, k).astype(jnp.int32)


def routed_drop_fraction(
    x: jax.Array,  # [B, T, D]
    p: dict,
    cfg: ModelConfig,
    capacity_factor: float = 2.0,
    ep: int = 1,
) -> float:
    """Diagnostic: fraction of REAL (token, expert-choice) assignments that
    overflowed their expert's static capacity (landed in the trash slot)
    for THIS batch — the drop-rate observability VERDICT r4 asks for.
    Mirrors the serving path's routing exactly, INCLUDING expert
    parallelism: with ``ep`` > 1 tokens are padded/split into per-shard
    blocks routed against the per-pair capacity ``_capacity(n_pad/ep)``,
    matching ``routed_moe_ffn``'s shard_fn (a global-capacity number would
    misstate what a multi-chip mesh actually drops). Host-returning; use
    on sample batches (bench/ablation), not inside a serving step."""
    b, t, d = x.shape
    n = b * t
    k = cfg.n_experts_used
    xf = x.reshape(n, d)
    if ep <= 1:
        cap = _capacity(n, cfg, capacity_factor)
        _, slot = _route(xf, p["router"], cfg, cap)
        return float(jnp.mean((slot == cfg.n_experts * cap).astype(jnp.float32)))
    n_pad = -(-n // ep) * ep
    blk = n_pad // ep
    c_pair = _capacity(blk, cfg, capacity_factor)
    if n_pad != n:
        xf = jnp.concatenate([xf, jnp.zeros((n_pad - n, d), xf.dtype)])
    blocks = xf.reshape(ep, blk, d)
    dropped = total = 0
    for s in range(ep):
        _, slot = _route(blocks[s], p["router"], cfg, c_pair)
        real = max(0, min(n - s * blk, blk))  # pads are appended at the end
        if real == 0:
            continue
        dropped += int(jnp.sum(slot[:real] == cfg.n_experts * c_pair))
        total += real * k
    return dropped / total if total else 0.0


def _expert_swiglu(xe: jax.Array, w_gate, w_up, w_down) -> jax.Array:
    """Batched per-expert SwiGLU. xe: [E_local, C, D]."""
    gate = jax.nn.silu(q_einsum("ecd,edf->ecf", xe, w_gate))
    up = q_einsum("ecd,edf->ecf", xe, w_up)
    return q_einsum("ecf,efd->ecd", gate * up, w_down)


def routed_moe_ffn(
    x: jax.Array,  # [B, T, D]
    p: dict,  # router / w_gate_e / w_up_e / w_down_e (arrays or QTensor)
    cfg: ModelConfig,
    mesh: Mesh | None = None,
    capacity_factor: float = 2.0,
) -> jax.Array:
    """Sparse top-k MoE FFN; expert-parallel when ``mesh`` has an ep axis."""
    b, t, d = x.shape
    n = b * t
    e, k = cfg.n_experts, cfg.n_experts_used
    xf = x.reshape(n, d)

    ep = mesh.shape.get(AXIS_EP, 1) if mesh is not None else 1
    if ep <= 1:
        # single-shard: one global slot buffer (+1 trash row for drops)
        cap = _capacity(n, cfg, capacity_factor)
        top_w, slot = _route(xf, p["router"], cfg, cap)
        top_w = top_w.astype(x.dtype)
        buf = jnp.zeros((e * cap + 1, d), x.dtype)
        buf = buf.at[slot.reshape(-1)].set(
            jnp.repeat(xf, k, axis=0), mode="drop", unique_indices=True
        )
        ye = _expert_swiglu(
            buf[: e * cap].reshape(e, cap, d), p["w_gate_e"], p["w_up_e"], p["w_down_e"]
        ).reshape(e * cap, d)
        ye = jnp.concatenate([ye, jnp.zeros((1, d), ye.dtype)])  # trash row -> 0
        picked = ye[slot.reshape(-1)].reshape(n, k, d)
        out = jnp.einsum("nkd,nk->nd", picked, top_w)
        return out.reshape(b, t, d)

    e_local = e // ep
    espec = P(AXIS_EP, None, None)

    # --- all-to-all dispatch: tokens sharded on ep -------------------------
    # pad N to a multiple of ep. Pad rows DO route (uniform top-k over the
    # zero vector) and DO occupy capacity slots — correctness rests on
    # ordering: pads are appended, so within the last shard's n-major
    # cumsum every pad position comes AFTER every real token's. Pads can
    # therefore overflow to trash but never displace a real assignment,
    # and their combined outputs are discarded by out[:n]. Do not reorder
    # the padding (interleaving or per-shard padding breaks this).
    n_pad = -(-n // ep) * ep
    c_pair = _capacity(n_pad // ep, cfg, capacity_factor)
    if n_pad != n:
        xf = jnp.concatenate([xf, jnp.zeros((n_pad - n, d), xf.dtype)])

    trash = ep * e_local * c_pair
    nspec = P(AXIS_EP, None)

    def shard_fn(xf, router, w_gate, w_up, w_down):
        # xf: this shard's N/ep token block (routing is genuinely LOCAL —
        # router/top_k/cumsum run on local tokens only); expert weights
        # sharded on ep (leading E axis), router replicated. Within a
        # shard the blocks=1 slot formula g*C_pair + pos IS the local
        # all-to-all send-buffer layout dst*(E_local*C_pair) + le*C_pair
        # + pos, with the same trash id E*C_pair.
        top_w, slot = _route(xf, router, cfg, c_pair)
        top_w = top_w.astype(xf.dtype)
        nl = xf.shape[0]
        buf = jnp.zeros((trash + 1, d), xf.dtype)
        buf = buf.at[slot.reshape(-1)].set(
            jnp.repeat(xf, k, axis=0), mode="drop", unique_indices=True
        )
        send = buf[:trash].reshape(ep, e_local * c_pair, d)
        # exchange slot payloads: recv[src] = src's tokens for MY experts
        recv = jax.lax.all_to_all(send, AXIS_EP, split_axis=0, concat_axis=0)
        xe = (
            recv.reshape(ep, e_local, c_pair, d)
            .transpose(1, 0, 2, 3)
            .reshape(e_local, ep * c_pair, d)
        )
        ye = _expert_swiglu(xe, w_gate, w_up, w_down)
        back = (
            ye.reshape(e_local, ep, c_pair, d)
            .transpose(1, 0, 2, 3)
            .reshape(ep, e_local * c_pair, d)
        )
        # return outputs to their sources; row layout matches `send`
        ret = jax.lax.all_to_all(back, AXIS_EP, split_axis=0, concat_axis=0)
        ret = jnp.concatenate([ret.reshape(trash, d), jnp.zeros((1, d), ye.dtype)])
        picked = ret[slot.reshape(-1)].reshape(nl, k, d)
        return jnp.einsum("nkd,nk->nd", picked, top_w)

    router_spec = jax.tree.map(lambda _: P(None, None), p["router"])
    out = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(nspec, router_spec, espec, espec, espec),
        out_specs=nspec,
    )(xf, p["router"], p["w_gate_e"], p["w_up_e"], p["w_down_e"])
    return out[:n].reshape(b, t, d)
