"""Gated-delta-rule linear-attention layers beside gated softmax-attention
layers, routed experts in every layer (``qwen3next``), next to
``models/llama.py``, ``models/mla_moe.py``, ``models/ssm_hybrid.py`` and
``models/swa_moe.py``.

``models.llama.forward`` / ``forward_decode_paged`` / ``make_cache`` /
``init_params`` hand a config whose ``family`` is ``gdn_moe`` to the twins
here. The file is ``ssm_hybrid``'s shape with another recurrence and another
FFN half, and takes that file's period scan (``ssm_hybrid._layers``), its
state's layout and its convolution as they are. What differs:

* **Three stacks**: ``blocks.linear`` [n_lin_layers, ...] and ``blocks.attn``
  [n_kv_layers, ...] hold the mixers by kind, ``blocks.moe`` [n_layers, ...]
  the FFN half of EVERY layer in model order (``models/experts.py``: a
  softmax router over ``n_experts``, the ``n_experts_held`` experts this chip
  holds of them, a shared expert behind a sigmoid gate).
* **A linear layer** (Gated DeltaNet): [q | k | v | z] = h W_qkvz and
  [b | a] = h W_ba (the published tensors interleave these by key head; the
  leaves here lie plainly, every part on a lane tile); q, k and v TOGETHER
  through a depthwise causal convolution of ``ssm_conv`` taps without bias,
  then SiLU; q and k L2-normalised a head, q times d_k^-0.5, a key head
  serving ``lin_v_heads / lin_k_heads`` value heads; beta = sigmoid(b),
  alpha = exp(-exp(A_log) softplus(a + dt_bias)) a value head; the gated delta
  rule over a float32 state [H_v, d_k, d_v] a slot (``ops/gated_delta.py``:
  chunked in prefill); the output normalised a head, times its gain and
  SiLU(z), then ``w_out``. A DECODE step of the layer is three products
  (``w_qkvz``, ``w_ba``, ``w_out``) and two Pallas calls between them:
  ``step_inputs`` (convolution step, L2 norms, gates, on the raw output of the
  products) and ``gated_delta_step`` (the rule on the slots that hold a
  request, a key head read once for its value heads, and the gated norm of
  what it reads out); ``linear_step_xla`` is the same step as the equations
  are written, which only the tests run. The state and the convolution's last
  ``ssm_conv`` raw inputs ride beside the caches as Mamba-2's do
  (``ops.kvcache.WithState``; ``ssm_hybrid``'s docstring has the rules for
  padding, for a slot without a request and for a replayed position), the
  inputs a tap a plane ([Ll, K, rows, C]: ``state_shapes`` says why).
* **An attention layer**: ``wq`` makes the queries AND an elementwise gate
  ([q | gate], each n_heads x head_dim; published interleaved by head); q and
  k are RMS-normalised a head (gains ``q_norm`` / ``k_norm``), the first
  ``rope_dim`` dims of a head rotated; causal softmax attention; the output
  times sigmoid(gate), then ``wo``. Head 256 is two lane tiles: the flash
  kernels and the paged decode kernel take it as it is.
* **Norm gains are plain**: the published gains are zero-centred, x (1 + w);
  a loader folds 1 + w into the leaf (``attention.norm_zero_centered`` in the
  GGUF header says so).

The state, alpha, beta, the L2 and gated norms, the router and softmax run in
float32; products take the weights' dtype as in the other families.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..ops import gated_delta, ssm_scan
from ..ops.flash_attention import (
    chunk_block_multiple,
    flash_attention_auto,
    flash_attention_chunk_auto,
)
from ..ops.kvcache import WithState, kv_pool_write_rows, kv_update_slice, table_rows_in_use
from ..ops.layers import gqa_attention, gqa_attention_hmajor, rms_norm, rope_cos_sin
from ..ops.wquant import flat_rows, mm
from .config import ModelConfig
from .experts import expert_path, moe_ffn, split_stacks, stats_width
from .ssm_hybrid import K_AXES, V_AXES, _embed, _layers, state_bytes, zeroed_state
from .swa_moe import _rotate

Params = dict[str, Any]


def state_shapes(cfg: ModelConfig, rows: int) -> tuple[tuple, tuple]:
    """((tail shape, seen shape), (state shape,)) for ``rows`` rows. The tails
    lie a tap a plane, [Ll, K, rows, C], as ``ssm_hybrid``'s do (its ``K_AXES``
    are this family's): a decode step shifts and weighs a tap of ALL the slots
    at once, the slots on the sublanes, and with the taps there instead (K = 4
    of a bf16 tile's rows) both XLA and a kernel gather row by row (PERF.md
    section 6, PR 48)."""
    ll = cfg.n_lin_layers
    return (((ll, cfg.ssm_conv, rows, cfg.lin_conv_dim), (rows,)),
            ((rows, ll, cfg.lin_v_heads, cfg.lin_k_dim, cfg.lin_v_dim),))


def make_state(cfg: ModelConfig, rows: int):
    """Zeroed state for ``rows`` rows: (K's ``st``, its axes), (V's, its)."""
    return zeroed_state(cfg, state_shapes(cfg, rows))


def state_bytes_per_slot(cfg: ModelConfig) -> int:
    """Device bytes one slot's state takes (what admission prices a slot at
    beside its KV blocks)."""
    return state_bytes(cfg, state_shapes(cfg, 1))


def make_cache(cfg: ModelConfig, batch: int, seq_len: int | None = None,
               dtype: str | None = None):
    """Zeroed row caches [B, n_kv_layers, Hkv, S, D], each with its rows'
    zeroed state beside it."""
    if cfg.kv_quant == "int8":
        raise NotImplementedError(
            "TPU_KV_QUANT=int8 is not implemented for linear-attention models: "
            "the family's caches ride with a float32 state that has no scale leaf")
    s = seq_len or cfg.max_seq_len
    dt = jnp.dtype(dtype or cfg.dtype)
    shape = (batch, cfg.n_kv_layers, cfg.n_kv_heads, s, cfg.head_dim)
    return tuple(WithState(jnp.zeros(shape, dt), st, ax) for st, ax in make_state(cfg, batch))


# ---------------------------------------------------------------------------
# the linear layer
# ---------------------------------------------------------------------------


def _project_in(h: jax.Array, p: Params, cfg: ModelConfig):
    """(qkv, z, b, a): the convolution's channels, the output gate, and the
    write strength's and the decay's inputs a value head."""
    qkvz, ba = mm(h, p["w_qkvz"]), mm(h, p["w_ba"])
    c = cfg.lin_conv_dim
    return qkvz[..., :c], qkvz[..., c:], ba[..., : cfg.lin_v_heads], ba[..., cfg.lin_v_heads:]


def _split_conv(qkv: jax.Array, cfg: ModelConfig):
    """The convolved channels as (q, k) [.., H_v, d_k] f32, normalised, a key
    head repeated for the value heads it serves, and v [.., H_v, d_v]."""
    kd = cfg.lin_k_heads * cfg.lin_k_dim
    lead = qkv.shape[:-1]
    q = gated_delta.l2_normalise(qkv[..., :kd].reshape(lead + (cfg.lin_k_heads, cfg.lin_k_dim)))
    k = gated_delta.l2_normalise(
        qkv[..., kd: 2 * kd].reshape(lead + (cfg.lin_k_heads, cfg.lin_k_dim)))
    v = qkv[..., 2 * kd:].reshape(lead + (cfg.lin_v_heads, cfg.lin_v_dim))
    rep = cfg.lin_v_heads // cfg.lin_k_heads
    return (jnp.repeat(q * cfg.lin_k_dim**-0.5, rep, axis=-2), jnp.repeat(k, rep, axis=-2), v)


def _gates(b: jax.Array, a: jax.Array, p: Params):
    """(beta, log alpha) [.., H_v] f32."""
    dt = jax.nn.softplus(a.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    return jax.nn.sigmoid(b.astype(jnp.float32)), -jnp.exp(p["a_log"].astype(jnp.float32)) * dt


def _mixer_out(o: jax.Array, z: jax.Array, p: Params, cfg: ModelConfig):
    """o [.., H_v, d_v] f32 -> the mixer's output: normalised a head, times
    the gain and silu(z), then the output projection."""
    return mm(gated_delta.gated_norm(o, z, p["gate_norm"], cfg.rms_eps), p["w_out"])


def linear_prefill(h, p: Params, cfg: ModelConfig, tails, states, layer, valid):
    """The mixer over T positions of B rows: ``tails`` [Ll, K, B, C] and
    ``states`` [B, Ll, H_v, d_k, d_v] are the rows' state of all layers, this
    one's slice read and written at ``layer``. ``valid`` [B]: real positions
    of each row."""
    t = h.shape[1]
    zero = jnp.zeros((), jnp.int32)
    qkv, z, b, a = _project_in(h, p, cfg)
    tail = jax.lax.dynamic_index_in_dim(tails, layer, axis=0, keepdims=False)
    qkv, tail = ssm_scan.causal_conv(qkv, tail, p["conv_w"], None, valid)
    tails = jax.lax.dynamic_update_slice(tails, tail[None], (layer, zero, zero, zero))
    q, k, v = _split_conv(qkv, cfg)
    real = (jnp.arange(t, dtype=jnp.int32)[None, :] < valid[:, None])[..., None]
    beta, log_alpha = _gates(b, a, p)
    s0 = jax.lax.dynamic_slice_in_dim(states, layer, 1, axis=1)[:, 0]
    o, s1 = gated_delta.gated_delta_chunked(
        q, k, v, jnp.where(real, log_alpha, 0.0), jnp.where(real, beta, 0.0), s0)
    states = jax.lax.dynamic_update_slice(states, s1[:, None], (zero, layer, zero, zero, zero))
    return _mixer_out(o, z, p, cfg), tails, states


def linear_step_xla(h, p: Params, cfg: ModelConfig, tails, states, layer, live, fresh):
    """``linear_step`` as the equations are written, in plain XLA over every
    slot (what the step's kernels are held to in the tests; no program runs
    it). ``fresh`` [B] bool."""
    zero = jnp.zeros((), jnp.int32)
    qkv, z, b, a = _project_in(h[:, 0], p, cfg)
    tail = jax.lax.dynamic_index_in_dim(tails, layer, axis=0, keepdims=False)
    qkv, tail = ssm_scan.conv_step(qkv, tail, p["conv_w"], None, fresh)
    tails = jax.lax.dynamic_update_slice(tails, tail[None], (layer, zero, zero, zero))
    q, k, v = _split_conv(qkv, cfg)
    beta, log_alpha = _gates(b, a, p)
    decay = jnp.where(fresh[:, None], jnp.exp(log_alpha), 1.0)
    beta = jnp.where(fresh[:, None], beta, 0.0)
    s = jax.lax.dynamic_index_in_dim(states, layer, axis=1, keepdims=False)
    o, s1 = gated_delta.gated_delta_recurrent(
        q[:, None], k[:, None], v[:, None], jnp.log(decay)[:, None], beta[:, None], s)
    on = live.mask[:, None, None, None]
    states = jax.lax.dynamic_update_index_in_dim(states, jnp.where(on, s1, s), layer, axis=1)
    o = jnp.where(live.mask[:, None, None], o[:, 0], 0.0)
    return _mixer_out(o, z, p, cfg)[:, None], tails, states


def linear_step(h, p: Params, cfg: ModelConfig, tails, states, layer, live, consts):
    """The mixer over ONE position of the ``live`` slots (``ssm_scan.
    LiveSlots``), their state updated in place in the pool: three products
    and two Pallas calls. ``consts`` (``gated_delta.step_consts``, once a
    step) holds the small leaves' whole stacks and the ``fresh`` flags: live
    rows that consume their position (the other live rows read their state as
    it is; a row that is not live gives zeros). Of ``p`` only the three
    projections are read."""
    x = h[:, 0]
    qkvz, ba = mm(x, p["w_qkvz"]), mm(x, p["w_ba"])
    heads = (cfg.lin_k_heads, cfg.lin_k_dim, cfg.lin_v_heads, cfg.lin_v_dim)
    tail = jax.lax.dynamic_index_in_dim(tails, layer, axis=0, keepdims=False)
    tail, q, k, v, decay, beta = gated_delta.step_inputs_auto(qkvz, ba, tail, layer, consts, heads)
    tails = jax.lax.dynamic_update_index_in_dim(tails, tail, layer, axis=0)
    states, y = gated_delta.gated_delta_step_auto(
        states, layer, live, decay, beta, q, k,
        gated_delta.Values(v, qkvz, consts.gain, consts.eps))
    return mm(y, p["w_out"])[:, None], tails, states


# ---------------------------------------------------------------------------
# the attention layer
# ---------------------------------------------------------------------------


def _qkvg(h, p: Params, cfg: ModelConfig, table):
    """q [B,T,H,D] and k [B,T,Hkv,D] normalised and rotated, v, and the gate
    [B,T,H x D] f32 (None without ``attn_out_gate``)."""
    b, t, _ = h.shape
    hd = cfg.n_heads * cfg.head_dim
    qg, k, v = flat_rows(mm(h, p["wq"]), mm(h, p["wk"]), mm(h, p["wv"]))
    q = qg[..., :hd].reshape(b, t, cfg.n_heads, cfg.head_dim)
    gate = jax.nn.sigmoid(qg[..., hd:].astype(jnp.float32)) if cfg.attn_out_gate else None
    k = k.reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q, k = rms_norm(q, p["q_norm"], cfg.rms_eps), rms_norm(k, p["k_norm"], cfg.rms_eps)
    return _rotate(q, table), _rotate(k, table), v, gate


def _attn_out(o, gate, p: Params):
    """[B,T,H,D] -> the layer's attention output: each element times its gate, wo."""
    o = o.reshape(o.shape[0], o.shape[1], -1)
    if gate is not None:
        o = o * gate.astype(o.dtype)
    return mm(o, p["wo"])


def _rope_table(cfg: ModelConfig, positions: jax.Array):
    dims = cfg.rope_dim or cfg.head_dim
    return rope_cos_sin(positions, dims, cfg.rope_theta) + (dims,)


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------


def _experts(params: Params, cfg: ModelConfig, rows: int, live, mesh):
    """The FFN half ``_layers`` runs in every layer: the routed experts of
    ``blocks.moe`` at the layer's place in the model. Where they take the hit
    list or the grouped form the three expert stacks are closed over WHOLE
    and a layer passes its place in them (``mla_moe._layers`` says why). The
    carry's last entry collects the layers' counters (None without ``live``)."""
    moe = params["blocks"]["moe"]
    form, whole = expert_path(cfg, rows, moe, mesh), None
    if form != "dense":
        whole, moe = split_stacks(moe)

    def ffn(x, carry, place):
        pf = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, place, axis=0, keepdims=False), moe)
        with jax.named_scope("ffn"):
            y, st = moe_ffn(rms_norm(x, pf["ffn_norm"], cfg.rms_eps), pf, cfg, live, form,
                            whole, place)
            if st is not None:
                with jax.named_scope("router"):  # the layer's counters, beside the others
                    carry = carry[:-1] + (jax.lax.dynamic_update_slice(
                        carry[-1], st[None], (place, jnp.zeros((), jnp.int32))),)
        return x + y, carry

    return ffn


def forward(
    params: Params, cfg: ModelConfig, tokens: jax.Array,
    k_cache: WithState, v_cache: WithState,
    start_pos: jax.Array, attn_window: int | None = None, mesh=None,
    ring_slot=None, logit_positions=None, fresh_prefill: bool = False,
    uniform_start: bool = False,
):
    """``models.llama.forward``'s contract over row caches with state: T
    positions of B rows that go on from the rows' state (zeros at a start;
    a chunk after the first finds what the chunk before left). The state
    that comes back is the one after each row's last REAL position:
    ``logit_positions + 1`` positions of a row are real (all T without it;
    none where it is negative: a row whose prompt ended in an earlier chunk
    of a group)."""
    if ring_slot is not None:
        raise NotImplementedError(
            "linear-attention models are served on the paged pool (KV_PAGED=1): the "
            "shared-ring cache layout rolls rows, and a state cannot be rolled")
    b, t = tokens.shape
    s_max = k_cache.shape[3]
    win = attn_window if (attn_window is not None and attn_window < s_max) else s_max
    positions = start_pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    valid = (jnp.full((b,), t, jnp.int32) if logit_positions is None
             else jnp.clip(logit_positions.astype(jnp.int32) + 1, 0, t))
    zero = jnp.zeros((), jnp.int32)
    flash = cfg.use_flash_attention and t > 1
    # the chunk kernel tiles the cache window: its extent must divide
    on_cache = flash and uniform_start and not fresh_prefill and (
        win % chunk_block_multiple(False, jnp.dtype(cfg.dtype).itemsize) == 0)
    with jax.named_scope("seq/attn"):
        table = _rope_table(cfg, positions)
        key_pos = jnp.arange(t if fresh_prefill else win, dtype=jnp.int32)
        mask = key_pos[None, None, :] <= positions[:, :, None]
    (tails, seen), (states,) = k_cache.st, v_cache.st

    def linear(h, p, carry, layer):
        kc, vc, tails, states, stats = carry
        out, tails, states = linear_prefill(h, p, cfg, tails, states, layer, valid)
        return out, (kc, vc, tails, states, stats)

    def attention(h, p, carry, layer):
        kc, vc, tails, states, stats = carry
        q, k, v, gate = _qkvg(h, p, cfg, table)

        def write(cache_b, rows_b, s):  # [L, Hkv, S, D] <- [Hkv, T, D] at (layer, 0, s, 0)
            return kv_update_slice(cache_b, rows_b[None], (layer, zero, s, zero))

        kc = jax.vmap(write)(kc, k.transpose(0, 2, 1, 3), start_pos)
        vc = jax.vmap(write)(vc, v.transpose(0, 2, 1, 3), start_pos)

        def window(cache):  # the layer's [B, Hkv, win, D]
            return jax.lax.dynamic_slice(
                cache, (zero, layer, zero, zero, zero),
                (b, 1, cfg.n_kv_heads, win, cfg.head_dim))[:, 0].astype(q.dtype)

        if fresh_prefill:  # start_pos == 0: the fresh keys are all there is
            o = (flash_attention_auto(q, k, v, cfg.attn_scale) if flash
                 else gqa_attention(q, k, v, mask, cfg.attn_scale))
        elif on_cache:
            o = flash_attention_chunk_auto(q, window(kc), window(vc), cfg.attn_scale, start_pos[0])
        else:
            o = gqa_attention_hmajor(q, window(kc), window(vc), mask, cfg.attn_scale)
        return _attn_out(o, gate, p), (kc, vc, tails, states, stats)

    x, (kc, vc, tails, states, _) = _layers(
        params, cfg, _embed(params, cfg, tokens),
        (k_cache.kv, v_cache.kv, tails, states, None),
        {"linear": linear, "attention": attention}, _experts(params, cfg, b * t, None, mesh))
    from .llama import lm_head_logits

    at = None if logit_positions is None else jnp.maximum(logit_positions, 0)
    logits = lm_head_logits(params, cfg, x, at, t)
    # a row with no real position here (its prompt ended in an earlier chunk
    # of its group) has consumed nothing more
    with jax.named_scope("seq/linear"):
        seen = jnp.where(valid > 0, start_pos + valid, seen).astype(jnp.int32)
    return logits, WithState(kc, (tails, seen), K_AXES), WithState(vc, (states,), V_AXES)


def forward_decode_paged(
    params: Params, cfg: ModelConfig, tokens: jax.Array,
    k_pool: WithState, v_pool: WithState,  # pools [NB, Lkv, Hkv, T, D] + the slots' state
    tbl: jax.Array, start_pos: jax.Array, mesh=None,
):
    """``models.llama.forward_decode_paged``'s contract, one position a slot:
    the attention layers write their row into the pool and attend over the
    slot's table (the paged decode kernel), the linear layers update the state
    in place of the slots that hold a request, those whose row of ``tbl``
    names a block. Row i of the batch IS slot i of the state. Returns
    (logits, k_pool, v_pool, the layers' expert counters [n_layers,
    ``experts.stats_width``] over those slots)."""
    from ..ops.paged_attention import paged_decode_attention_auto

    b, w = tokens.shape
    if w != 1:
        raise NotImplementedError(
            "linear-attention models decode one position a step: a speculative "
            "bundle would advance the state past the drafts that are rejected, and "
            "the pool keeps no snapshot to go back to (SPEC_DECODE=0)")
    (tails, seen), (states,) = k_pool.st, v_pool.st
    # one list for all the layers of the step (and of the burst: ``tbl`` is
    # the launch's, and no step changes it)
    with jax.named_scope("seq/linear"):
        live = ssm_scan.live_slots(table_rows_in_use(tbl))
        fresh = live.mask & (start_pos >= seen)
        # once a step, not once a layer: the small leaves as the kernels read them
        consts = (gated_delta.step_consts(params["blocks"]["linear"], cfg.rms_eps, fresh)
                  if cfg.n_lin_layers else None)
    with jax.named_scope("seq/attn"):
        table = _rope_table(cfg, start_pos[:, None])

    def linear(h, p, carry, layer):
        kp, vp, tails, states, stats = carry
        out, tails, states = linear_step(h, p, cfg, tails, states, layer, live, consts)
        return out, (kp, vp, tails, states, stats)

    def attention(h, p, carry, layer):
        kp, vp, tails, states, stats = carry
        q, k, v, gate = _qkvg(h, p, cfg, table)
        kp = kv_pool_write_rows(kp, k, tbl, start_pos, layer)
        vp = kv_pool_write_rows(vp, v, tbl, start_pos, layer)
        o = paged_decode_attention_auto(q, kp, vp, tbl, start_pos, layer, cfg.attn_scale)
        return _attn_out(o, gate, p), (kp, vp, tails, states, stats)

    stats = jnp.zeros((cfg.n_moe_layers, stats_width(cfg)), jnp.int32)
    x, (kp, vp, tails, states, stats) = _layers(
        params, cfg, _embed(params, cfg, tokens), (k_pool.kv, v_pool.kv, tails, states, stats),
        {"linear": linear, "attention": attention},
        _experts(params, cfg, b * w, live.mask.astype(jnp.float32), mesh))
    from .llama import lm_head_logits

    logits = lm_head_logits(params, cfg, x, None, w)
    with jax.named_scope("seq/linear"):
        seen = jnp.where(fresh, start_pos + 1, seen).astype(jnp.int32)
    return (logits, WithState(kp, (tails, seen), K_AXES), WithState(vp, (states,), V_AXES),
            stats)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Random small-scale init; the tree is what a loader of the family would
    build (``benchmark/references/gdn_moe.py param_shapes`` names it).
    ``a_log`` and ``dt_bias`` start where Mamba-2 starts them (A in [1, 16],
    dt in [1e-3, 1e-1]: alpha from 0.2 to 0.999 a token); the seeded weights
    of the benchmark draw them anew, by the reference's ``weight_gains``."""
    dt = jnp.dtype(cfg.dtype)
    keys = iter(jax.random.split(key, 48))

    def rand(*shape):
        return (jax.random.normal(next(keys), shape, jnp.float32) * 0.02).astype(dt)

    d, hd, L = cfg.d_model, cfg.head_dim, cfg.n_layers
    ll, la = cfg.n_lin_layers, cfg.n_kv_layers
    hv, c, vd = cfg.lin_v_heads, cfg.lin_conv_dim, cfg.lin_v_heads * cfg.lin_v_dim
    blocks: Params = {}
    if ll:
        dt0 = jnp.exp(jax.random.uniform(next(keys), (ll, hv), jnp.float32,
                                         jnp.log(1e-3), jnp.log(1e-1)))
        blocks["linear"] = {
            "mix_norm": jnp.ones((ll, d), dt),
            "w_qkvz": rand(ll, d, c + vd), "w_ba": rand(ll, d, 2 * hv),
            "conv_w": rand(ll, cfg.ssm_conv, c) * 10,
            "dt_bias": (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(dt),  # softplus^-1
            "a_log": jnp.log(jax.random.uniform(
                next(keys), (ll, hv), jnp.float32, 1.0, 16.0)).astype(dt),
            "gate_norm": jnp.ones((ll, cfg.lin_v_dim), dt),
            "w_out": rand(ll, vd, d),
        }
    if la:
        gate = 2 if cfg.attn_out_gate else 1
        blocks["attn"] = {
            "mix_norm": jnp.ones((la, d), dt),
            "wq": rand(la, d, gate * cfg.n_heads * hd), "wk": rand(la, d, cfg.n_kv_heads * hd),
            "wv": rand(la, d, cfg.n_kv_heads * hd), "wo": rand(la, cfg.n_heads * hd, d)}
        if cfg.qk_norm:
            blocks["attn"] |= {"q_norm": jnp.ones((la, hd), dt), "k_norm": jnp.ones((la, hd), dt)}
    e, eh, fe, fs = cfg.n_experts, cfg.n_experts_held, cfg.moe_d_ff, cfg.n_shared_experts * cfg.moe_d_ff
    blocks["moe"] = {
        "ffn_norm": jnp.ones((L, d), dt), "router": rand(L, d, e),
        "w_gate_e": rand(L, eh, d, fe), "w_up_e": rand(L, eh, d, fe),
        "w_down_e": rand(L, eh, fe, d),
        "w_gate_s": rand(L, d, fs), "w_up_s": rand(L, d, fs), "w_down_s": rand(L, fs, d)}
    if cfg.router_scoring != "softmax":
        blocks["moe"]["e_bias"] = rand(L, e)
    if cfg.shared_gate:
        blocks["moe"]["shared_gate"] = rand(L, d)
    params: Params = {"embed": rand(cfg.vocab_size, d), "out_norm": jnp.ones((d,), dt),
                      "blocks": blocks}
    if not cfg.tie_embeddings:
        params["lm_head"] = rand(d, cfg.vocab_size)
    return params
