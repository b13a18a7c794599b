"""The routed-expert layer four model files share: routed experts beside
always-on shared ones, dropless (``models/mla_moe.py`` with latent attention,
``models/swa_moe.py`` with window attention: sigmoid scores with a selection
bias; ``models/gdn_moe.py`` with linear attention: softmax scores, a sigmoid
gate on the shared expert, and a share of the experts on this chip;
``models/ssm_hybrid.py`` with state-space layers: sigmoid scores, a share, and
two-matrix experts in a latent).

A stack of such layers holds ``router`` [L, d, E], ``e_bias`` [L, E] (sigmoid
scoring only), the expert stacks ``w_gate_e`` / ``w_up_e`` [L, E_held, d, f],
``w_down_e`` [L, E_held, f, d], the shared expert's ``w_gate_s`` / ``w_up_s``
/ ``w_down_s`` and, with ``cfg.shared_gate``, its gate ``shared_gate`` [L, d].
``expert_path`` names the form a call takes from its shapes and leaf types
alone; ``moe_ffn`` computes it (``ops/moe_experts.py`` has the two kernels).

**Two-matrix experts** (``cfg.mlp_act == "relu2"``): an expert, routed or
shared, is ``relu(x W_up)^2 W_down`` and the stack holds no ``w_gate_*``.
**Experts in a latent** (``cfg.moe_latent``): the stack also holds ONE pair
``w_lat_down`` [L, d, latent] / ``w_lat_up`` [L, latent, d] a layer; the
routed experts' matrices are [latent, f] / [f, latent] and they compute
``w_lat_down``'s output, their gated sum going through ``w_lat_up``; the
router and the shared expert read the hidden state itself.

**A share of the experts** (``cfg.moe_ep_size`` chips share a layer, this one
is ``cfg.moe_ep_rank``): the router keeps its E outputs and its top-k over
all of them, the gates are normalised over all k picks, and the stacks hold
the E / size experts e with e mod size == rank, expert e at place e // size.
A pick of an expert that lives elsewhere adds nothing here: what comes back
is this chip's partial sum (its picks + the shared expert), and nothing
stands in for the other chips or their exchange.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..ops.layers import swiglu
from ..ops.wquant import mm, q_einsum
from .config import ModelConfig

Params = dict[str, Any]

_HI = jax.lax.Precision.HIGHEST
# what the [rows, experts, width] activations of one group of experts in the
# dense dispatch may take: a prefill of many rows computes its experts in
# several groups, a decode step in one
_EXPERT_ACT_BYTES = 256 << 20


def route(h: jax.Array, p: Params, cfg: ModelConfig):
    """(idx [B, T, k], gate [B, T, k] f32): the k experts with the largest
    sigmoid score + selection bias, gated by their normalised scores times
    ``routed_scaling`` (the bias picks, it does not weigh); or, with
    ``cfg.router_scoring == "softmax"`` and no bias leaf, the k largest of a
    softmax over all the experts, renormalised over the k."""
    logits = jnp.einsum("btd,de->bte", h.astype(jnp.float32),
                        p["router"].astype(jnp.float32), precision=_HI)
    if cfg.router_scoring == "softmax" and "e_bias" not in p:
        chosen, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.n_experts_used)
        gate = chosen / jnp.sum(chosen, axis=-1, keepdims=True) * cfg.routed_scaling
        return idx, gate
    score = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(score + p["e_bias"].astype(jnp.float32), cfg.n_experts_used)
    chosen = jnp.take_along_axis(score, idx, axis=-1)
    gate = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20) * cfg.routed_scaling
    return idx, gate


EXPERT_LEAVES = ("w_gate_e", "w_up_e", "w_down_e")


def split_stacks(moe: Params) -> tuple[tuple, Params]:
    """(the WHOLE expert stacks the kernels index by (layer, expert): gate, up,
    down, the gate None for two-matrix experts; the stack's other leaves)."""
    return (tuple(moe.get(k) for k in EXPERT_LEAVES),
            {k: v for k, v in moe.items() if k not in EXPERT_LEAVES})


def relu2(x: jax.Array) -> jax.Array:
    return jnp.square(jax.nn.relu(x))


def _shared(h: jax.Array, p: Params, cfg: ModelConfig) -> jax.Array:
    if cfg.mlp_act == "relu2":
        return mm(relu2(mm(h, p["w_up_s"])), p["w_down_s"])
    return swiglu(h, p["w_gate_s"], p["w_up_s"], p["w_down_s"], cfg.mlp_act)


def stats_width(cfg: ModelConfig) -> int:
    """Counters a layer a step ``moe_ffn`` gives: (experts hit, most rows on
    one, live rows) and, where the chip holds a share of the experts, the
    live rows' picks that landed on an expert held here."""
    return 4 if cfg.moe_ep_size > 1 else 3


def held_place(idx: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Picks ``idx`` (expert ids of the whole layer) as places in this chip's
    stacks: e // size where e mod size is this chip's rank, else
    ``n_experts_held``, one past the last place (an absent expert)."""
    size = cfg.moe_ep_size
    return jnp.where(idx % size == cfg.moe_ep_rank, idx // size, cfg.n_experts_held)


def expert_path(cfg: ModelConfig, rows: int, stack: Params, mesh=None) -> str:
    """``"hit_list"``, ``"grouped"`` or ``"dense"``: the form the routed
    experts of a call of ``rows`` rows take, from what the call can see and
    nothing else. With plain expert leaves on one device: the hit list where
    the (row, expert) picks are fewer than the layer's experts (a decode step
    of 8 rows x top-4 under 64: some experts are certainly not hit, and
    reading is all a few rows cost), the grouped form from there up (a
    prefill chunk, a chunk group, a verify bundle: the picks sorted by expert
    and each computed on its own expert only). There is no row count under
    which dense dispatch is the better of the two: it reads all 64 experts
    whatever was picked, 2.0-2.1 ms a layer on a v5e, where the grouped form
    reads and computes what was picked (PERF.md, PR 32: the layer alone at
    16-1,024 rows). Quantised stacks (``WQUANT`` makes ``w_*_e`` QTensors)
    and meshes of more than one chip keep the dense dispatch until a cell
    measures them."""
    plain = all(isinstance(stack[k], jax.Array) for k in EXPERT_LEAVES if k in stack)
    one_device = mesh is None or mesh.size == 1
    if not (plain and one_device):
        return "dense"
    # a chip that holds 1 / size of the experts sees 1 / size of the picks
    # under a balanced router: the same comparison, both sides over size
    return "hit_list" if rows * cfg.n_experts_used < cfg.n_experts else "grouped"


def moe_ffn(h: jax.Array, p: Params, cfg: ModelConfig, live: jax.Array | None = None,
            form: str = "dense", stacks=None, place=None):
    """Routed experts + the shared expert(s), dropless, in the ``form`` that
    ``expert_path`` named, called inside the layer's ``ffn`` scope (its words
    here: ``router``, ``shared``, ``experts``). Returns (y, stats): ``stats``
    is int32 [3] = (distinct experts the ``live`` rows hit, most rows on one
    expert, live rows) when ``live`` [B] is given, else None.

    Every form is the same sum: each row's k picked experts, weighted by
    their gates, in float32 on top of the shared expert's output. The hit
    list and the grouped form take ``stacks``, the three WHOLE expert stacks
    [L, E, ., .], and ``place``, this layer's place in them
    (``ops/moe_experts.py``). With ``cfg.moe_latent`` the routed experts
    compute the layer's latent down-projection of ``h`` (scope
    ``latent_down``) and their sum goes through its up-projection
    (``latent_up``) before the shared expert's output is added.

    **Hit list**: the experts the live rows hit are listed on the device and
    only those are read, each once, every row gated by its own weight on that
    expert (0 for a row that did not pick it): the dense dispatch's sums
    without the experts whose terms are all zero. A row of a slot that holds
    no request adds nothing to the list; its output is whatever the listed
    experts give it, and the batcher discards it.

    **Grouped**: the rows x k (row, pick) pairs are sorted by expert, each
    pair's row is multiplied by its own expert's matrices only, scaled by
    its gate, and a row's k results are gathered back and summed: the dense
    dispatch's sums without ANY term that is zero, rows x k products where
    it makes rows x E. Every row is computed, live or not.

    **Dense dispatch** (``p`` holds the layer's own expert leaves): every
    expert computes every row, in groups of experts that are STATIC slices of
    the layer's stacks: a slice of a scan's slice still fuses into the
    product that reads it, where an inner ``lax.scan`` over the groups made
    XLA copy a layer's 1.4 GB of experts into the loop's operand every step
    (46 ms a decode step for 15: PERF.md, PR 29). A prefill splits so that
    [rows, group, width] stays under ``_EXPERT_ACT_BYTES``."""
    e = cfg.n_experts_held
    share = cfg.moe_ep_size > 1
    with jax.named_scope("router"):
        idx, gate = route(h, p, cfg)
        if share:
            # places in this chip's stacks; an absent expert's one-hot is all
            # zeros, so it is in no sum below
            idx = held_place(idx, cfg)
        picked = jax.nn.one_hot(idx, e, dtype=jnp.float32)  # [B, T, k, E]
        combine = jnp.sum(picked * gate[..., None], axis=-2)  # [B, T, E] f32
        on = jnp.sum(picked, axis=-2)  # [B, T, E]: 1 where a row picked the expert
        rows_on = jnp.sum(on if live is None else on * live[:, None, None], axis=(0, 1))
        stats = None if live is None else jnp.stack(
            [jnp.sum(rows_on > 0), jnp.max(rows_on), jnp.sum(live) * h.shape[1]]
            + ([jnp.sum(rows_on)] if share else [])).astype(jnp.int32)
    with jax.named_scope("shared"):
        acc = _shared(h, p, cfg).astype(jnp.float32)
        if cfg.shared_gate:
            acc = acc * jax.nn.sigmoid(jnp.einsum(
                "btd,d->bt", h.astype(jnp.float32), p["shared_gate"].astype(jnp.float32),
                precision=_HI))[..., None]
    if cfg.moe_latent:
        with jax.named_scope("latent_down"):
            x = mm(h, p["w_lat_down"])
        with jax.named_scope("experts"):
            r = _routed(x, jnp.zeros(x.shape, jnp.float32), p, cfg, form, stacks, place,
                        idx, gate, combine, on, rows_on)
        with jax.named_scope("latent_up"):
            return (acc + mm(r.astype(h.dtype), p["w_lat_up"])).astype(h.dtype), stats
    with jax.named_scope("experts"):
        return _routed(h, acc, p, cfg, form, stacks, place,
                       idx, gate, combine, on, rows_on).astype(h.dtype), stats


def _routed(h, acc, p: Params, cfg: ModelConfig, form, stacks, place,
            idx, gate, combine, on, rows_on):
    """``acc`` [B, T, w] f32 + the rows' picked experts of ``h`` [B, T, w],
    gated, in ``form`` (``moe_ffn`` has the forms and the router's outputs)."""
    e, k = cfg.n_experts_held, cfg.n_experts_used
    rows = h.shape[0] * h.shape[1]
    if form != "dense":
        from ..ops import moe_experts
    if form == "hit_list":
        ids, n_hit = moe_experts.hit_list(rows_on, min(e, rows * k))
        gates = jnp.take(combine.reshape(rows, e).T, ids, axis=0)  # [places, rows]
        acc = moe_experts.moe_hit_experts_auto(
            h.reshape(rows, -1), gates, ids, n_hit, place, *stacks,
            acc.reshape(rows, -1)).reshape(acc.shape)
        return acc
    if form == "grouped":
        order, at = moe_experts.sort_by_expert(idx.reshape(rows, k))
        y = moe_experts.moe_grouped_experts_auto(
            jnp.take(h.reshape(rows, -1), order // k, axis=0), gate.reshape(-1)[order],
            jnp.sum(on, axis=(0, 1)), place, *stacks)  # [rows x k, d] f32, sorted
        y = y[at]  # [rows, k, d]: each row's picks
        if cfg.moe_ep_size > 1:
            # an absent expert's pairs sort last, past every expert's
            # rows: no visit of the kernel wrote them
            y = jnp.where((idx.reshape(rows, k) < e)[..., None], y, 0.0)
        return acc + jnp.sum(y, axis=1).reshape(acc.shape)
    combine = combine.astype(h.dtype)
    act_bytes = rows * e * cfg.moe_d_ff * h.dtype.itemsize
    groups = next(g for g in range(1, e + 1)
                  if e % g == 0 and act_bytes // g <= _EXPERT_ACT_BYTES or g == e)
    size = e // groups
    for g in range(groups):
        wg, wu, wd = (jax.tree.map(lambda x: x[g * size: (g + 1) * size], p.get(k_))
                      for k_ in EXPERT_LEAVES)
        act = (relu2(q_einsum("btd,gdf->btgf", h, wu)) if wg is None else
               jax.nn.silu(q_einsum("btd,gdf->btgf", h, wg)) * q_einsum("btd,gdf->btgf", h, wu))
        act = act * combine[..., g * size: (g + 1) * size, None]
        acc = acc + q_einsum("btgf,gfd->btd", act, wd).astype(jnp.float32)
    return acc
