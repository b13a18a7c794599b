"""State-space (Mamba-2) layers beside grouped-query attention layers without
positional embedding (``granitehybrid``; ``nemotron_h`` with a layer ONE
sublayer, a mixer or routed experts), next to ``models/llama.py`` and
``models/mla_moe.py``.

``models.llama.forward`` / ``forward_decode_paged`` / ``make_cache`` /
``init_params`` hand a config whose ``family`` is ``ssm_hybrid`` to the twins
here, so the batcher, the block pool, the table and the sampling are the ones
every other family uses. What differs:

* **Two stacks by kind**, ``blocks.mamba`` [n_ssm_layers, ...] and
  ``blocks.attn`` [n_kv_layers, ...], and a static plan of the period that
  ``cfg.layer_types`` repeats (granite-4.0-h: 5 mamba, 1 attention, 4 mamba,
  four times): ONE ``lax.scan`` over the periods and, inside it, one over
  each run of layers of a kind, each layer taking its weights out of the
  whole stacks at its own place, so a program's text holds three layers
  (one a run) and no weight is copied.
* **Only the attention layers hold KV**: the pool's layer axis is
  ``cfg.n_kv_layers``. A kv head of 64 is not a row the paged decode kernel
  can copy (and the device pads it to 128 lanes anyway), so the caches hold
  ``cfg.kv_pack`` = 2 kv heads side by side in one 128-lane row
  (``cfg.kv_cache_dims``): a query is zero-padded onto its own head's half,
  so its scores see that half alone, and the matching half of the output is
  kept. ``ops/paged_attention.py`` serves it unchanged, at twice the (tiny)
  attention arithmetic and no extra byte.
* **A mamba layer keeps a state in place of KV**, indexed by slot and not by
  table: ``ops.kvcache.WithState`` carries it beside each cache of the pair.
  K's ``st`` is (the convolution's last ``ssm_conv`` raw inputs
  [n_ssm_layers, K, rows, conv_dim], layer-major as the layer scan writes
  them and a tap a plane with the rows on the sublanes (``state_shapes``),
  ``seen`` [rows] int32: how many positions the state has consumed),
  V's ``st`` is (the state [rows, n_ssm_layers, H / k, N, k P] float32,
  ``ops/ssm_scan.py``'s plane).
  Prefill runs the chunked scan and returns the state after the last REAL
  position of each row (``logit_positions + 1`` positions are real: padding
  neither decays nor feeds a state and is never in a convolution tail).
  Decode updates the pool in place, one Pallas call a layer, and only where
  a slot holds a request: the slots whose row of the block table names a
  block (``ops.kvcache.table_rows_in_use``) are listed once a launch
  (``ops.ssm_scan.live_slots``) and every layer's call moves those slots'
  state alone. An empty slot, and one a chunked admit has reserved and not
  finished, has no block yet: its state, tail and ``seen`` come out of a
  launch as they went in, and its row of the mixer's output is zeros. A live
  slot whose position is one the state has consumed already (``start_pos <
  seen``: the batcher replays the last prompt position of a request that
  wants its first token masked or with log-probabilities) reads its state
  and does not advance it.

* **A layer may be one sublayer** (``"experts"`` among ``cfg.layer_types``:
  ``nemotron_h``): a mamba or an attention layer then has no MLP behind it,
  and a layer of the third kind is the routed-expert block of
  ``models/experts.py`` alone, from a third stack ``blocks.moe``
  [n_moe_layers, ...]: a sigmoid router over ``n_experts``, two-matrix relu^2
  experts in a latent of ``cfg.moe_latent`` columns, of which the chip holds
  ``n_experts_held``, a shared expert at the hidden width. The plan scans a
  pair of kinds that repeats (mamba, experts) as it scans a run of one kind.
  With ``cfg.ssm_n_groups`` > 1, B and C are a group's ([G, N] a token, head
  h reading group h // (H / G)) and the gated norm normalises each group's
  ``d_inner / G`` channels alone.

The state, dt, the decays, the gated norm and the router run in float32;
products take the weights' dtype as in the other families.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from ..ops import ssm_scan
from ..ops.kvcache import WithState, kv_pool_write_rows, kv_update_slice, table_rows_in_use
from ..ops.layers import apply_rope, gqa_attention_hmajor, rms_norm, rope_cos_sin, swiglu
from ..ops.wquant import flat_rows, mm
from .config import ModelConfig
from .experts import expert_path, moe_ffn, split_stacks, stats_width

Params = dict[str, Any]


def period_plan(cfg: ModelConfig) -> tuple[int, tuple[str, ...]]:
    """(periods, the kinds of one period's layers): the shortest prefix of
    ``layer_types`` that, repeated, gives all of it."""
    kinds = cfg.layer_types
    n = len(kinds)
    if n != cfg.n_layers:
        raise ValueError(f"layer_types names {n} layers, n_layers is {cfg.n_layers}")
    if "experts" in kinds and not cfg.moe_latent:
        raise NotImplementedError(
            "layers of routed experts alone work in a latent (moe_latent > 0): "
            "two-matrix experts at the hidden width are not implemented")
    p = next(p for p in range(1, n + 1) if n % p == 0 and kinds == kinds[:p] * (n // p))
    return n // p, kinds[:p]


# where the leaves of K's and V's ``st`` have their row axis
K_AXES, V_AXES = (2, 0), (0,)


def state_shapes(cfg: ModelConfig, rows: int) -> tuple[tuple, tuple]:
    """((tail shape, seen shape), (state shape,)) for ``rows`` rows. The tails
    lie a tap a plane, [Lm, K, rows, C]: a decode step shifts and weighs a tap
    of ALL the slots at once, the slots on the sublanes. With the taps there
    instead (K = 4 of a bf16 tile's 16 rows) the device gathers them row by
    row in every layer (PERF.md section 6, PR 48 and PR 57)."""
    lm = cfg.n_ssm_layers
    plane = ssm_scan.state_plane(cfg.ssm_n_heads, cfg.ssm_head_dim, cfg.ssm_d_state)
    return (((lm, cfg.ssm_conv, rows, cfg.ssm_conv_dim), (rows,)), ((rows, lm) + plane,))


def zeroed_state(cfg: ModelConfig, shapes, k_axes=K_AXES):
    """Zeroed state of ``shapes`` (a family's ``state_shapes``): (K's ``st``,
    its axes ``k_axes``), (V's, its): the K leaf in the serving dtype and
    ``seen`` beside K, the float32 state beside V."""
    (tail, seen), (plane,) = shapes
    return (((jnp.zeros(tail, jnp.dtype(cfg.dtype)), jnp.zeros(seen, jnp.int32)), k_axes),
            ((jnp.zeros(plane, jnp.float32),), V_AXES))


def state_bytes(cfg: ModelConfig, shapes) -> int:
    """Device bytes of a state of ``shapes``."""
    (tail, seen), (plane,) = shapes
    return (math.prod(tail) * jnp.dtype(cfg.dtype).itemsize + 4 * math.prod(seen)
            + math.prod(plane) * 4)


def make_state(cfg: ModelConfig, rows: int):
    """Zeroed state for ``rows`` rows: (K's ``st``, its axes), (V's, its)."""
    return zeroed_state(cfg, state_shapes(cfg, rows))


def state_bytes_per_slot(cfg: ModelConfig) -> int:
    """Device bytes one slot's state takes (what admission prices a slot at
    beside its KV blocks)."""
    return state_bytes(cfg, state_shapes(cfg, 1))


def make_cache(cfg: ModelConfig, batch: int, seq_len: int | None = None,
               dtype: str | None = None):
    """Zeroed row caches [B, n_kv_layers, Hkv / pack, S, pack x D], each with
    its rows' zeroed state beside it."""
    if cfg.kv_quant == "int8":
        raise NotImplementedError(
            "TPU_KV_QUANT=int8 is not implemented for state-space models: two kv "
            "heads share a cache row, and one scale a row cannot serve both")
    s = seq_len or cfg.max_seq_len
    dt = jnp.dtype(dtype or cfg.dtype)
    (h, w), _ = cfg.kv_cache_dims()
    shape = (batch, cfg.n_kv_layers, h, s, w)
    return tuple(WithState(jnp.zeros(shape, dt), st, ax) for st, ax in make_state(cfg, batch))


# ---------------------------------------------------------------------------
# the two mixers
# ---------------------------------------------------------------------------


def _project_in(h: jax.Array, p: Params, cfg: ModelConfig):
    """(z, xBC, dt) = W_in h. The published W_in's 2 d_inner + 2 G N + H
    columns are two leaves here, ``w_in`` [d, d_inner + conv_dim] (z | xBC)
    and ``w_dt`` [d, H]: 8,512 columns are not whole 128-lane tiles (the
    device re-lays such a stack out on every dispatch, 1.25 GB of copies),
    8,448 are, and z and xBC start on a tile."""
    zx = mm(h, p["w_in"])
    return zx[..., : cfg.ssm_d_inner], zx[..., cfg.ssm_d_inner:], mm(h, p["w_dt"])


def _split_conv(xbc: jax.Array, cfg: ModelConfig):
    """x [.., H, P] and B, C [.., N] (one group) or [.., G, N]."""
    di, g = cfg.ssm_d_inner, cfg.ssm_n_groups
    n = g * cfg.ssm_d_state
    x = xbc[..., :di].reshape(xbc.shape[:-1] + (cfg.ssm_n_heads, cfg.ssm_head_dim))
    bm, cm = xbc[..., di: di + n], xbc[..., di + n:]
    if g > 1:
        bm, cm = (z.reshape(z.shape[:-1] + (g, cfg.ssm_d_state)) for z in (bm, cm))
    return x, bm, cm


def _dt(dt_raw: jax.Array, p: Params) -> jax.Array:
    return jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))


def _a(p: Params) -> jax.Array:
    return -jnp.exp(p["a_log"].astype(jnp.float32))


def _mixer_out(y: jax.Array, x: jax.Array, z: jax.Array, p: Params, cfg: ModelConfig):
    """y [.., H, P] f32 (C . S) -> the mixer's output: + D x, gated by silu(z)
    BEFORE the norm over all of d_inner (over each group's channels alone
    where there are groups), then the output projection."""
    y = y + p["d_skip"].astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    y = y.reshape(y.shape[:-2] + (cfg.ssm_d_inner,)) * jax.nn.silu(z.astype(jnp.float32))
    gain, g = p["gate_norm"].astype(jnp.float32), cfg.ssm_n_groups
    if g > 1:
        y = rms_norm(y.reshape(y.shape[:-1] + (g, -1)), gain.reshape(g, -1),
                     cfg.rms_eps).reshape(y.shape)
    else:
        y = rms_norm(y, gain, cfg.rms_eps)
    return mm(y.astype(z.dtype), p["w_out"])


def mamba_prefill(h, p: Params, cfg: ModelConfig, tails, states, layer, valid):
    """The mixer over T positions of B rows: ``tails`` [Lm, K, B, C] and
    ``states`` [B, Lm, H/k, N, kP] are the rows' state of all layers, this
    one's slice read and written at ``layer``. ``valid`` [B]: real positions
    of each row."""
    b, t, _ = h.shape
    zero = jnp.zeros((), jnp.int32)
    z, xbc, dt_raw = _project_in(h, p, cfg)
    tail = jax.lax.dynamic_index_in_dim(tails, layer, axis=0, keepdims=False)
    xbc, tail = ssm_scan.causal_conv(xbc, tail, p["conv_w"], p["conv_b"], valid)
    tails = jax.lax.dynamic_update_slice(tails, tail[None], (layer, zero, zero, zero))
    x, bm, cm = _split_conv(xbc, cfg)
    real = jnp.arange(t, dtype=jnp.int32)[None, :] < valid[:, None]
    dt = jnp.where(real[..., None], _dt(dt_raw, p), 0.0)
    k = ssm_scan.heads_per_row(cfg.ssm_n_heads, cfg.ssm_head_dim)
    s0 = ssm_scan.unpack_state(jax.lax.dynamic_slice_in_dim(states, layer, 1, axis=1)[:, 0], k)
    y, s1 = ssm_scan.ssd_chunked(x, dt, _a(p), bm, cm, s0, cfg.ssm_chunk)
    states = jax.lax.dynamic_update_slice(
        states, ssm_scan.pack_state(s1, k)[:, None], (zero, layer, zero, zero, zero))
    return _mixer_out(y, x, z, p, cfg), tails, states


def mamba_step(h, p: Params, cfg: ModelConfig, tails, states, layer, live, fresh):
    """The mixer over ONE position of the ``live`` slots (``ssm_scan.
    LiveSlots``), their state updated in place in the pool. ``fresh`` [B]
    bool: live rows that consume their position (the other live rows read
    their state as it is; a row that is not live gives zeros)."""
    zero = jnp.zeros((), jnp.int32)
    z, xbc, dt_raw = _project_in(h[:, 0], p, cfg)
    tail = jax.lax.dynamic_index_in_dim(tails, layer, axis=0, keepdims=False)
    xbc, tail = ssm_scan.conv_step(xbc, tail, p["conv_w"], p["conv_b"], fresh)
    tails = jax.lax.dynamic_update_slice(tails, tail[None], (layer, zero, zero, zero))
    x, bm, cm = _split_conv(xbc, cfg)
    dt = _dt(dt_raw, p)
    decay = jnp.where(fresh[:, None], jnp.exp(dt * _a(p)), 1.0)
    dtx = jnp.where(fresh[:, None, None], dt[..., None] * x.astype(jnp.float32), 0.0)
    states, y = ssm_scan.ssm_state_step_auto(states, layer, live, decay, dtx, bm, cm)
    return _mixer_out(y, x, z, p, cfg)[:, None], tails, states


def _qkv(h, p: Params, cfg: ModelConfig, positions):
    b, t, _ = h.shape
    q, k, v = flat_rows(mm(h, p["wq"]), mm(h, p["wk"]), mm(h, p["wv"]))
    q = q.reshape(b, t, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
    if cfg.use_rope:
        cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    return q, k, v


def pack_kv(x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """[B, T, Hkv, D] -> [B, T, Hkv / pack, pack x D]: neighbours share a row."""
    b, t, h, d = x.shape
    return x.reshape(b, t, h // cfg.kv_pack, cfg.kv_pack * d)


def _own_part(cfg: ModelConfig, dtype) -> jax.Array:
    """[Hq, pack] one-hot: the part of a packed row that holds a query
    head's own kv head."""
    part = (jnp.arange(cfg.n_heads) // (cfg.n_heads // cfg.n_kv_heads)) % cfg.kv_pack
    return jax.nn.one_hot(part, cfg.kv_pack, dtype=dtype)


def pack_q(q: jax.Array, cfg: ModelConfig) -> jax.Array:
    """[B, T, Hq, D] -> [B, T, Hq, pack x D]: a query on its kv head's part of
    the packed row, zeros on the others'."""
    if cfg.kv_pack == 1:
        return q
    on = _own_part(cfg, q.dtype)
    return (q[..., None, :] * on[:, :, None]).reshape(q.shape[:-1] + (-1,))


def unpack_o(o: jax.Array, cfg: ModelConfig) -> jax.Array:
    """[B, T, Hq, pack x D] -> [B, T, Hq, D]: the part of the query's own head."""
    if cfg.kv_pack == 1:
        return o
    o = o.reshape(o.shape[:-1] + (cfg.kv_pack, cfg.head_dim))
    return jnp.sum(o * _own_part(cfg, o.dtype)[:, :, None], axis=-2)


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------


def _embed(params: Params, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    with jax.named_scope("embed"):
        return params["embed"][tokens].astype(jnp.dtype(cfg.dtype)) * cfg.embedding_scale


# a layer kind's stack under ``blocks``, and the scope its sublayer runs in
STACK = {"mamba": "mamba", "attention": "attn", "linear": "linear",
         "lightning": "linear", "sparse": "attn", "experts": "moe"}
SCOPE = {"mamba": "seq/ssm", "attention": "seq/attn", "linear": "seq/linear",
         "lightning": "seq/linear", "sparse": "seq/sparse", "experts": "ffn"}


def period_runs(kinds: tuple[str, ...]) -> list[tuple[tuple[str, ...], int, int]]:
    """One period's layers as runs (unit, times, first layer of the run in
    the period): a kind that repeats, or a PAIR of kinds that repeats at least
    twice ((mamba, experts) x 3), whichever covers more layers from where the
    run starts; a scan's text holds the unit once."""
    runs, j = [], 0
    while j < len(kinds):
        def times(u):
            c = 1
            while kinds[j + c * u: j + (c + 1) * u] == kinds[j: j + u]:
                c += 1
            return c

        one, two = times(1), times(2)
        u, c = (2, two) if two >= 2 and kinds[j] != kinds[j + 1] and 2 * two > one else (1, one)
        runs.append((kinds[j: j + u], c, j))
        j += u * c
    return runs


def _layers(params: Params, cfg: ModelConfig, x, carry, mixers, ffn=None, stacks=None):
    """All layers in model order: one scan over the periods, and inside a
    period one scan over each run of ``period_runs`` (granite-4.0-h: 5
    mamba, 1 attention, 4 mamba), so a program's text holds one unit of a
    run and not the period's ten. ``mixers[kind](h, p, carry, layer) -> (out,
    carry)`` are the caller's, one a kind of ``cfg.layer_types``; a ``layer``
    is the layer's place in its own kind's stack (``STACK``; ``stacks`` gives
    a kind another tree than the whole stack: the experts' without the
    leaves their kernels index themselves).

    The FFN half is the dense SwiGLU whose leaves lie in the mixer's stack,
    or the caller's: ``ffn(x, carry, place) -> (x, carry)`` with ``place`` the
    layer's place in the model (``models/gdn_moe.py``: routed experts in a
    stack of their own, one entry a layer); or none at all where a layer is
    one sublayer (``"experts"`` among the kinds)."""
    periods, kinds = period_plan(cfg)
    single = "experts" in kinds
    per = {kind: kinds.count(kind) for kind in mixers}
    stacks = {kind: params["blocks"].get(STACK[kind]) for kind in mixers} | (stacks or {})

    def one(c, kind, layer, place):
        x, carry = c
        # ONE slice a weight, out of the whole stack at the layer's own
        # place (as a scan over the stack would take it): the dot reads it
        # where it lies. A static slice of a scan's slice of a period's
        # layers is materialised instead, every weight copied every step.
        p = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, layer, axis=0, keepdims=False),
            stacks[kind])
        with jax.named_scope(SCOPE[kind]):
            out, carry = mixers[kind](rms_norm(x, p["mix_norm"], cfg.rms_eps), p, carry, layer)
            x = x + out * cfg.residual_scale
        if single:
            return x, carry
        if ffn is not None:
            return ffn(x, carry, place())
        with jax.named_scope("ffn/mlp"):
            h = rms_norm(x, p["ffn_norm"], cfg.rms_eps)
            x = x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"], cfg.mlp_act) * cfg.residual_scale
        return x, carry

    def period(c, i):
        for unit, times, start in period_runs(kinds):
            # each kind's first layer of the run among its kind: the period's,
            # then the run's (a unit names a kind once)
            base = {kind: i * per[kind] + kinds[:start].count(kind) for kind in unit}

            def units(c, j, unit=unit, base=base, start=start):
                """The unit's layers at turn ``j`` of its run (None: a run of
                one turn, of one kind). ``place`` is read by a caller's ``ffn``
                alone; a unit of one kind adds no ``j * 1 + 0`` to it, which
                would be two more operations in every such program's text."""
                def place(k):
                    first = i * len(kinds) + start
                    if len(unit) == 1:
                        return first if j is None else first + j
                    return first + j * len(unit) + k

                for k, kind in enumerate(unit):
                    c = one(c, kind, base[kind] if j is None else base[kind] + j,
                            lambda k=k: place(k))
                return c

            if times == 1:
                c = units(c, None)
            else:
                c, _ = jax.lax.scan(lambda c, j, units=units: (units(c, j), None),
                                    c, jnp.arange(times, dtype=jnp.int32))
        return c, None

    (x, carry), _ = jax.lax.scan(period, (x, carry), jnp.arange(periods, dtype=jnp.int32))
    return x, carry


def _experts(params: Params, cfg: ModelConfig, rows: int, live, mesh):
    """(the ``"experts"`` kind's sublayer for ``_layers``, its ``stacks``
    entry): the routed experts of ``blocks.moe`` at the layer's place in that
    stack. Where they take the hit list or the grouped form the expert stacks
    are closed over WHOLE and a layer passes its place in them
    (``mla_moe._layers`` says why). The carry's last entry collects the
    layers' counters (None without ``live``)."""
    moe = params["blocks"]["moe"]
    form, whole = expert_path(cfg, rows, moe, mesh), None
    if form != "dense":
        whole, moe = split_stacks(moe)

    def experts(h, p, carry, layer):
        y, st = moe_ffn(h, p, cfg, live, form, whole, layer)
        if st is not None:
            with jax.named_scope("router"):  # the layer's counters, beside the others
                carry = carry[:-1] + (jax.lax.dynamic_update_slice(
                    carry[-1], st[None], (layer, jnp.zeros((), jnp.int32))),)
        return y, carry

    return {"experts": experts}, {"experts": moe}


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
            "state-space models are served on the paged pool (KV_PAGED=1): the "
            "shared-ring cache layout rolls rows, and a state cannot be rolled")
    del uniform_start
    b, t = tokens.shape
    s_max = k_cache.shape[3]
    win = attn_window if (attn_window is not None and attn_window < s_max) else s_max
    positions = start_pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    valid = (jnp.full((b,), t, jnp.int32) if logit_positions is None
             else jnp.clip(logit_positions.astype(jnp.int32) + 1, 0, t))
    zero = jnp.zeros((), jnp.int32)
    with jax.named_scope("seq/attn"):
        key_pos = jnp.arange(t if fresh_prefill else win, dtype=jnp.int32)
        mask = key_pos[None, None, :] <= positions[:, :, None]
    x = _embed(params, cfg, tokens)
    (tails, seen), (states,) = k_cache.st, v_cache.st

    def mamba(h, p, carry, layer):
        kc, vc, tails, states, stats = carry
        out, tails, states = mamba_prefill(h, p, cfg, tails, states, layer, valid)
        return out, (kc, vc, tails, states, stats)

    def attention(h, p, carry, layer):
        kc, vc, tails, states, stats = carry
        q, k, v = _qkv(h, p, cfg, positions)

        def write(cache_b, rows_b, s):  # [L, H', S, D'] <- [H', T, D'] at (layer, 0, s, 0)
            return kv_update_slice(cache_b, rows_b[None], (layer, zero, s, zero))

        kc = jax.vmap(write)(kc, pack_kv(k, cfg).transpose(0, 2, 1, 3), start_pos)
        vc = jax.vmap(write)(vc, pack_kv(v, cfg).transpose(0, 2, 1, 3), start_pos)
        if fresh_prefill:  # start_pos == 0: the fresh keys are all there is
            ks, vs = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
        else:
            def window(cache):  # the layer's [B, Hkv, win, D], unpacked
                hp, w = cache.shape[2], cache.shape[4]
                sl = jax.lax.dynamic_slice(
                    cache, (zero, layer, zero, zero, zero), (b, 1, hp, win, w))[:, 0]
                sl = sl.reshape(b, hp, win, cfg.kv_pack, cfg.head_dim).transpose(0, 1, 3, 2, 4)
                return sl.reshape(b, cfg.n_kv_heads, win, cfg.head_dim).astype(q.dtype)

            ks, vs = window(kc), window(vc)
        o = gqa_attention_hmajor(q, ks, vs, mask, cfg.attn_scale)
        return mm(o.reshape(b, t, -1), p["wo"]), (kc, vc, tails, states, stats)

    experts, stacks = _experts(params, cfg, b * t, None, mesh) if cfg.n_moe_layers else ({}, None)
    x, (kc, vc, tails, states, _) = _layers(
        params, cfg, x, (k_cache.kv, v_cache.kv, tails, states, None),
        {"mamba": mamba, "attention": attention} | experts, stacks=stacks)
    from .llama import lm_head_logits

    at = None if logit_positions is None else jnp.maximum(logit_positions, 0)
    logits = lm_head_logits(params, cfg, x, at, t)
    # a row with no real position here (its prompt ended in an earlier chunk
    # of its group) has consumed nothing more
    with jax.named_scope("seq/ssm"):
        seen = jnp.where(valid > 0, start_pos + valid, seen).astype(jnp.int32)
    return logits, WithState(kc, (tails, seen), K_AXES), WithState(vc, (states,), V_AXES)


def forward_decode_paged(
    params: Params, cfg: ModelConfig, tokens: jax.Array,
    k_pool: WithState, v_pool: WithState,  # pools [NB, Lkv, H', T, D'] + the slots' state
    tbl: jax.Array, start_pos: jax.Array, mesh=None,
):
    """``models.llama.forward_decode_paged``'s contract, one position a slot:
    the attention layers write their packed row into the pool and attend over
    the slot's table (the paged decode kernel), the mamba layers update the
    state in place of the slots that hold a request, those whose row of
    ``tbl`` names a block. Row i of the batch IS slot i of the state. With
    layers of experts also returns their counters [n_moe_layers,
    ``experts.stats_width``] over those slots."""
    from ..ops.paged_attention import paged_decode_attention_auto

    b, w = tokens.shape
    if w != 1:
        raise NotImplementedError(
            "state-space models decode one position a step: a speculative bundle "
            "would advance the state past the drafts that are rejected, and the "
            "pool keeps no snapshot to go back to (SPEC_DECODE=0)")
    (tails, seen), (states,) = k_pool.st, v_pool.st
    # one list for all the layers of the step (and of the burst: ``tbl`` is
    # the launch's, and no step changes it)
    with jax.named_scope("seq/ssm"):
        live = ssm_scan.live_slots(table_rows_in_use(tbl))
        fresh = live.mask & (start_pos >= seen)
    positions = start_pos[:, None]
    x = _embed(params, cfg, tokens)

    def mamba(h, p, carry, layer):
        kp, vp, tails, states, stats = carry
        out, tails, states = mamba_step(h, p, cfg, tails, states, layer, live, fresh)
        return out, (kp, vp, tails, states, stats)

    def attention(h, p, carry, layer):
        kp, vp, tails, states, stats = carry
        q, k, v = _qkv(h, p, cfg, positions)
        kp = kv_pool_write_rows(kp, pack_kv(k, cfg), tbl, start_pos, layer)
        vp = kv_pool_write_rows(vp, pack_kv(v, cfg), tbl, start_pos, layer)
        o = paged_decode_attention_auto(pack_q(q, cfg), kp, vp, tbl, start_pos, layer,
                                        cfg.attn_scale)
        return mm(unpack_o(o, cfg).reshape(b, w, -1), p["wo"]), (kp, vp, tails, states, stats)

    experts, stacks, stats = {}, None, None
    if cfg.n_moe_layers:
        experts, stacks = _experts(params, cfg, b * w, live.mask.astype(jnp.float32), mesh)
        stats = jnp.zeros((cfg.n_moe_layers, stats_width(cfg)), jnp.int32)
    x, (kp, vp, tails, states, stats) = _layers(
        params, cfg, x, (k_pool.kv, v_pool.kv, tails, states, stats),
        {"mamba": mamba, "attention": attention} | experts, stacks=stacks)
    from .llama import lm_head_logits

    logits = lm_head_logits(params, cfg, x, None, w)
    with jax.named_scope("seq/ssm"):
        seen = jnp.where(fresh, start_pos + 1, seen).astype(jnp.int32)
    pools = (WithState(kp, (tails, seen), K_AXES), WithState(vp, (states,), V_AXES))
    return (logits,) + pools + (() if stats is None else (stats,))


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Random small-scale init; the tree is what a loader of the family would
    build (``benchmark/references/ssm_hybrid.py param_shapes`` names it).
    ``a_log``, ``dt_bias`` and ``d_skip`` start where Mamba-2 starts them
    (A in [1, 16], dt in [1e-3, 1e-1], D = 1): the seeded weights of the
    benchmark draw them anew, by the reference's ``weight_gains``."""
    dt = jnp.dtype(cfg.dtype)
    keys = iter(jax.random.split(key, 32))

    def rand(*shape):
        return (jax.random.normal(next(keys), shape, jnp.float32) * 0.02).astype(dt)

    d, ff, hd = cfg.d_model, cfg.d_ff, cfg.head_dim
    lm, la = cfg.n_ssm_layers, cfg.n_kv_layers
    h, di, c = cfg.ssm_n_heads, cfg.ssm_d_inner, cfg.ssm_conv_dim

    def common(L: int) -> Params:
        if cfg.n_moe_layers:  # one sublayer a layer: no MLP behind a mixer
            return {"mix_norm": jnp.ones((L, d), dt)}
        return {"mix_norm": jnp.ones((L, d), dt), "ffn_norm": jnp.ones((L, d), dt),
                "w_gate": rand(L, d, ff), "w_up": rand(L, d, ff), "w_down": rand(L, ff, d)}

    blocks: Params = {}
    if lm:
        dt0 = jnp.exp(jax.random.uniform(next(keys), (lm, h), jnp.float32,
                                         jnp.log(1e-3), jnp.log(1e-1)))
        blocks["mamba"] = common(lm) | {
            "w_in": rand(lm, d, di + c), "w_dt": rand(lm, d, h),
            "conv_w": rand(lm, cfg.ssm_conv, c) * 10, "conv_b": rand(lm, c),
            "dt_bias": (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(dt),  # softplus^-1
            "a_log": jnp.log(jax.random.uniform(
                next(keys), (lm, h), jnp.float32, 1.0, 16.0)).astype(dt),
            "d_skip": jnp.ones((lm, h), dt),
            "gate_norm": jnp.ones((lm, di), dt),
            "w_out": rand(lm, di, d),
        }
    if la:
        blocks["attn"] = common(la) | {
            "wq": rand(la, d, cfg.n_heads * hd), "wk": rand(la, d, cfg.n_kv_heads * hd),
            "wv": rand(la, d, cfg.n_kv_heads * hd), "wo": rand(la, cfg.n_heads * hd, d)}
    if cfg.n_moe_layers:
        # two-matrix experts in a latent (models/experts.py); the shared one
        # at the hidden width
        le, e, eh = cfg.n_moe_layers, cfg.n_experts, cfg.n_experts_held
        w, fe, fs = cfg.moe_latent, cfg.moe_d_ff, cfg.n_shared_experts * cfg.moe_d_ff
        blocks["moe"] = {
            "mix_norm": jnp.ones((le, d), dt), "router": rand(le, d, e), "e_bias": rand(le, e),
            "w_up_e": rand(le, eh, w, fe), "w_down_e": rand(le, eh, fe, w),
            "w_up_s": rand(le, d, fs), "w_down_s": rand(le, fs, d),
            "w_lat_down": rand(le, d, w), "w_lat_up": rand(le, w, d)}
    params: Params = {"embed": rand(cfg.vocab_size, d), "out_norm": jnp.ones((d,), dt),
                      "blocks": blocks}
    if not cfg.tie_embeddings:
        params["lm_head"] = rand(d, cfg.vocab_size)
    return params
