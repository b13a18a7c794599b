"""Per-device HBM accounting for a sharded serving config.

Answers "does this model fit this mesh?" *before* touching a device — the
fail-fast the 70B-on-v5e-8 story needs (BASELINE.md config 3: 8 x 16 GB HBM;
140 GB of bf16 weights only fit after weight-only int8). Mirrors
``sharding.param_sharding_rules`` axis-for-axis: any change there must be
reflected here (test_wquant.py pins the 70B budget).
"""

from __future__ import annotations

from dataclasses import dataclass

from ..models.config import ModelConfig


@dataclass
class _Leaf:
    shape: tuple[int, ...]
    shard_axes: tuple[int, ...]  # which dims divide by (tp-or-ep) factors
    itemsize: int
    quantizable: bool = False


def _leaves(cfg: ModelConfig, dtype_bytes: int) -> dict[str, _Leaf]:
    d, hq, hkv, hd, ff, L, V = (
        cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
        cfg.d_ff, cfg.n_layers, cfg.vocab_size,
    )
    if cfg.is_mla:
        return _mla_leaves(cfg, dtype_bytes)
    if cfg.n_ssm_layers:
        return _ssm_leaves(cfg, dtype_bytes)
    if cfg.n_win_layers:
        return _swa_leaves(cfg, dtype_bytes)
    if cfg.is_sala:
        return _sala_leaves(cfg, dtype_bytes)
    if cfg.n_lin_layers:
        return _gdn_leaves(cfg, dtype_bytes)
    out: dict[str, _Leaf] = {
        "embed": _Leaf((V, d), (), dtype_bytes),
        "out_norm": _Leaf((d,), (), dtype_bytes),
        "lm_head": _Leaf((d, V), (1,), dtype_bytes, quantizable=True),
        "blocks.attn_norm": _Leaf((L, d), (), dtype_bytes),
        "blocks.ffn_norm": _Leaf((L, d), (), dtype_bytes),
        "blocks.wq": _Leaf((L, d, hq * hd), (2,), dtype_bytes, True),
        "blocks.wk": _Leaf((L, d, hkv * hd), (2,), dtype_bytes, True),
        "blocks.wv": _Leaf((L, d, hkv * hd), (2,), dtype_bytes, True),
        "blocks.wo": _Leaf((L, hq * hd, d), (1,), dtype_bytes, True),
    }
    if cfg.attn_bias:
        out |= {
            "blocks.bq": _Leaf((L, hq * hd), (1,), dtype_bytes),
            "blocks.bk": _Leaf((L, hkv * hd), (1,), dtype_bytes),
            "blocks.bv": _Leaf((L, hkv * hd), (1,), dtype_bytes),
        }
    if cfg.is_moe:
        e = cfg.n_experts
        out |= {
            "blocks.router": _Leaf((L, d, e), (), dtype_bytes),
            # dim1 divides by ep, the tp dim by tp (handled by caller factors)
            "blocks.w_gate_e": _Leaf((L, e, d, ff), (1, 3), dtype_bytes, True),
            "blocks.w_up_e": _Leaf((L, e, d, ff), (1, 3), dtype_bytes, True),
            "blocks.w_down_e": _Leaf((L, e, ff, d), (1, 2), dtype_bytes, True),
        }
    else:
        out |= {
            "blocks.w_gate": _Leaf((L, d, ff), (2,), dtype_bytes, True),
            "blocks.w_up": _Leaf((L, d, ff), (2,), dtype_bytes, True),
            "blocks.w_down": _Leaf((L, ff, d), (1,), dtype_bytes, True),
        }
    return out


def _mla_leaves(cfg: ModelConfig, dtype_bytes: int) -> dict[str, _Leaf]:
    """The leaves of ``models.mla_moe.init_params``, unsharded (the family
    serves on one chip a replica), by the form the configuration has: the
    query's pair or its one matrix, the mixers' maps only with more than one
    stream (their small leaves are left out)."""
    d, hq, V = cfg.d_model, cfg.n_heads, cfg.vocab_size
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rq, rkv, maps = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.hc_mult * (cfg.hc_mult + 2)
    out = {"embed": _Leaf((V, d), (), dtype_bytes),
           "lm_head": _Leaf((d, V), (), dtype_bytes, True)}
    stacks = (("dense", cfg.n_dense_layers, {"w_gate": (d, cfg.d_ff), "w_up": (d, cfg.d_ff),
                                              "w_down": (cfg.d_ff, d)}),
              ("moe", cfg.n_layers - cfg.n_dense_layers, {
                  "router": (d, cfg.n_experts),
                  "w_gate_e": (cfg.n_experts, d, cfg.moe_d_ff),
                  "w_up_e": (cfg.n_experts, d, cfg.moe_d_ff),
                  "w_down_e": (cfg.n_experts, cfg.moe_d_ff, d),
                  "w_gate_s": (d, cfg.n_shared_experts * cfg.moe_d_ff),
                  "w_up_s": (d, cfg.n_shared_experts * cfg.moe_d_ff),
                  "w_down_s": (cfg.n_shared_experts * cfg.moe_d_ff, d)}))
    from ..ops.wquant import quantizable

    for name, L, ffn in stacks:
        if not L:
            continue
        attn = {"w_dkv": (d, rkv + dr), "w_ukv": (rkv, hq * (dn + dv)), "wo": (hq * dv, d)}
        attn |= ({"w_dq": (d, rq), "w_uq": (rq, hq * (dn + dr))} if rq
                 else {"wq": (d, hq * (dn + dr))})
        if cfg.hc_mult > 1:
            attn |= {"hc_attn_w": (cfg.hc_mult * d, maps), "hc_ffn_w": (cfg.hc_mult * d, maps)}
        for k, shape in (attn | ffn).items():
            out[f"blocks.{name}.{k}"] = _Leaf((L,) + shape, (), dtype_bytes, quantizable(k))
    return out


def _ssm_leaves(cfg: ModelConfig, dtype_bytes: int) -> dict[str, _Leaf]:
    """The leaves of ``models.ssm_hybrid.init_params``, unsharded (the family
    serves on one chip a replica); the scan's small leaves are left out."""
    from ..ops.wquant import quantizable

    d, ff, V = cfg.d_model, cfg.d_ff, cfg.vocab_size
    hq, hkv, hd, di = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.ssm_d_inner
    out = {"embed": _Leaf((V, d), (), dtype_bytes),
           "lm_head": _Leaf((d, V), (), dtype_bytes, True)}
    ffn = {"w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)}
    stacks = [("mamba", cfg.n_ssm_layers, {
                  "w_in": (d, di + cfg.ssm_conv_dim), "w_dt": (d, cfg.ssm_n_heads),
                  "w_out": (di, d)}),
              ("attn", cfg.n_kv_layers, {"wq": (d, hq * hd), "wk": (d, hkv * hd),
                                         "wv": (d, hkv * hd), "wo": (hq * hd, d)})]
    if cfg.n_moe_layers:
        # one sublayer a layer: no MLP behind a mixer, and a third stack of
        # two-matrix experts (those held here) in a latent
        e, eh, w = cfg.n_experts, cfg.n_experts_held, cfg.moe_latent
        fe, fs = cfg.moe_d_ff, cfg.n_shared_experts * cfg.moe_d_ff
        ffn = {}
        stacks.append(("moe", cfg.n_moe_layers, {
            "router": (d, e), "w_lat_down": (d, w), "w_lat_up": (w, d),
            "w_up_e": (eh, w, fe), "w_down_e": (eh, fe, w),
            "w_up_s": (d, fs), "w_down_s": (fs, d)}))
    for name, L, mixer in stacks:
        for k, shape in (mixer | ffn).items() if L else ():
            out[f"blocks.{name}.{k}"] = _Leaf((L,) + shape, (), dtype_bytes, quantizable(k))
    return out


def _swa_leaves(cfg: ModelConfig, dtype_bytes: int) -> dict[str, _Leaf]:
    """The leaves of ``models.swa_moe.init_params``, unsharded (the family
    serves on one chip a replica); norms and the selection bias are left out."""
    from ..ops.wquant import quantizable

    d, V, hd, hkv = cfg.d_model, cfg.vocab_size, cfg.head_dim, cfg.n_kv_heads
    e, fe, fs = cfg.n_experts, cfg.moe_d_ff, cfg.n_shared_experts * cfg.moe_d_ff

    def attn(heads: int) -> dict:
        return {"wq": (d, heads * hd), "wk": (d, hkv * hd), "wv": (d, hkv * hd),
                "wo": (heads * hd, d)} | ({"wg": (d, heads)} if cfg.attn_gate else {})

    stacks = (("full", cfg.n_kv_layers, attn(cfg.n_heads)),
              ("win", cfg.n_win_layers, attn(cfg.win_n_heads)),
              ("dense", cfg.n_dense_layers, {"w_gate": (d, cfg.d_ff), "w_up": (d, cfg.d_ff),
                                             "w_down": (cfg.d_ff, d)}),
              ("moe", cfg.n_moe_layers, {
                  "router": (d, e), "w_gate_e": (e, d, fe), "w_up_e": (e, d, fe),
                  "w_down_e": (e, fe, d), "w_gate_s": (d, fs), "w_up_s": (d, fs),
                  "w_down_s": (fs, d)}))
    out = {"embed": _Leaf((V, d), (), dtype_bytes),
           "lm_head": _Leaf((d, V), (), dtype_bytes, True)}
    for name, L, leaves in stacks:
        for k, shape in leaves.items() if L else ():
            out[f"blocks.{name}.{k}"] = _Leaf((L,) + shape, (), dtype_bytes, quantizable(k))
    return out


def _gdn_leaves(cfg: ModelConfig, dtype_bytes: int) -> dict[str, _Leaf]:
    """The leaves of ``models.gdn_moe.init_params``, unsharded (the family
    serves on one chip a replica, which may be one chip's share of a layer's
    experts: ``n_experts_held``); norms and the small decay leaves are left
    out."""
    from ..ops.wquant import quantizable

    d, V, hd, hq, hkv = cfg.d_model, cfg.vocab_size, cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    e, eh, fe = cfg.n_experts, cfg.n_experts_held, cfg.moe_d_ff
    fs, vd = cfg.n_shared_experts * cfg.moe_d_ff, cfg.lin_v_heads * cfg.lin_v_dim
    stacks = (("linear", cfg.n_lin_layers, {
                  "w_qkvz": (d, cfg.lin_conv_dim + vd), "w_ba": (d, 2 * cfg.lin_v_heads),
                  "conv_w": (cfg.ssm_conv, cfg.lin_conv_dim), "w_out": (vd, d)}),
              ("attn", cfg.n_kv_layers, {
                  "wq": (d, (2 if cfg.attn_out_gate else 1) * hq * hd), "wk": (d, hkv * hd),
                  "wv": (d, hkv * hd), "wo": (hq * hd, d)}),
              ("moe", cfg.n_moe_layers, {
                  "router": (d, e), "w_gate_e": (eh, d, fe), "w_up_e": (eh, d, fe),
                  "w_down_e": (eh, fe, d), "w_gate_s": (d, fs), "w_up_s": (d, fs),
                  "w_down_s": (fs, d)}))
    out = {"embed": _Leaf((V, d), (), dtype_bytes),
           "lm_head": _Leaf((d, V), (), dtype_bytes, True)}
    for name, L, leaves in stacks:
        for k, shape in leaves.items() if L else ():
            out[f"blocks.{name}.{k}"] = _Leaf((L,) + shape, (), dtype_bytes, quantizable(k))
    return out


def _sala_leaves(cfg: ModelConfig, dtype_bytes: int) -> dict[str, _Leaf]:
    """The leaves of ``models.sala.init_params``, unsharded (the family serves
    on one chip a replica); norms and the decay leaf are left out."""
    from ..ops.wquant import quantizable

    d, V, ff, hd = cfg.d_model, cfg.vocab_size, cfg.d_ff, cfg.head_dim
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    kd, vd = cfg.lin_v_heads * cfg.lin_k_dim, cfg.lin_v_heads * cfg.lin_v_dim
    ffn = {"w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)}
    stacks = (("linear", cfg.n_lin_layers, ffn | {
                  "wq": (d, kd), "wk": (d, kd), "wv": (d, vd), "wg": (d, vd), "wo": (vd, d)}),
              ("attn", cfg.n_kv_layers, ffn | {
                  "wq": (d, 2 * hq * hd), "wk": (d, hkv * hd), "wv": (d, hkv * hd),
                  "wo": (hq * hd, d)}))
    out = {"embed": _Leaf((V, d), (), dtype_bytes),
           "lm_head": _Leaf((d, V), (), dtype_bytes, True)}
    for name, L, leaves in stacks:
        for k, shape in leaves.items() if L else ():
            out[f"blocks.{name}.{k}"] = _Leaf((L,) + shape, (), dtype_bytes, quantizable(k))
    return out


def state_slot_bytes(cfg: ModelConfig) -> int:
    """Device bytes of what ONE slot keeps beside its KV blocks (0 for a
    family that keeps nothing there): a state-space family's recurrent
    state, a window-attention family's rings. It is what admission prices a
    slot at whatever its context, where the pool's KV is priced by the block."""
    if cfg.n_ssm_layers:
        from ..models.ssm_hybrid import state_bytes_per_slot

        return state_bytes_per_slot(cfg)
    if cfg.n_win_layers:
        from ..models.swa_moe import ring_bytes_per_slot

        return ring_bytes_per_slot(cfg)
    if cfg.n_lin_layers:
        from ..models.llama import family_module

        return family_module(cfg).state_bytes_per_slot(cfg)
    return 0


def kv_token_values(cfg: ModelConfig) -> int:
    """Cached numbers a token a layer: K and V over the kv heads, or the
    latent and the rotary key of an MLA cache."""
    return sum(h * w for h, w in cfg.kv_cache_dims())


def estimate_device_bytes(
    cfg: ModelConfig,
    mesh_shape: dict[str, int],
    quant: str = "none",
    batch: int = 8,
    seq_len: int | None = None,
    cache_dtype_bytes: int | None = None,
    group: int = 32,
) -> dict[str, int]:
    """Estimated peak HBM bytes per device: params + KV cache + workspace.

    ``mesh_shape`` e.g. {"tp": 8} or {"dp": 2, "ep": 4}. Sharded axes divide
    by the product of the tensor-parallel-like factors exactly as
    ``param_sharding_rules`` assigns them (tp for dense, ep x tp for experts).
    A dp factor does NOT divide anything: dp serves as independent batcher
    replicas on disjoint device slices, so each device sees one replica's
    full weights-and-cache footprint — per-chip bytes at ``dp=2,tp=2``
    equal ``tp=2``. ``quant="int4"`` prices grouped QTensor4 storage: half
    a byte per code plus an f32 scale AND zero-point per ``group``
    contraction rows.
    """
    dtype_bytes = 2 if cfg.dtype in ("bfloat16", "float16") else 4
    tp = mesh_shape.get("tp", 1)
    ep = mesh_shape.get("ep", 1)
    seq = seq_len or cfg.max_seq_len
    # replicated-KV GQA fallback (sharding.kv_replicated): when tp cannot
    # divide the KV heads, wk/wv/bk/bv and the cache stay whole per chip
    kv_tp = tp if cfg.n_kv_heads % tp == 0 else 1
    _KV_LEAVES = ("blocks.wk", "blocks.wv", "blocks.bk", "blocks.bv")

    params = 0
    for name, leaf in _leaves(cfg, dtype_bytes).items():
        n = 1
        for dim in leaf.shape:
            n *= dim
        # divide by the mesh factor on each sharded axis. For experts the
        # first sharded axis is ep, the second tp; for dense leaves it is tp.
        t = kv_tp if name in _KV_LEAVES else tp
        factors = [ep, tp] if len(leaf.shard_axes) == 2 else [t] * len(leaf.shard_axes)
        for f in factors:
            n //= f
        if quant == "int8" and leaf.quantizable:
            w_bytes = n  # int8 codes
            # scale: one f32 per output channel (last axis), same sharding
            scale_elems = n // leaf.shape[-2] if len(leaf.shape) >= 2 else 0
            params += w_bytes + scale_elems * 4
        elif quant == "int4" and leaf.quantizable:
            # packed nibbles: half a byte per code; scale + zero-point:
            # one f32 pair per group of contraction rows (wquant degrades
            # the group to divide small contraction axes — same here)
            from ..ops.wquant import effective_group

            g = effective_group(leaf.shape[-2], group)
            meta_elems = (n // leaf.shape[-2]) * (leaf.shape[-2] // g)
            params += n // 2 + meta_elems * 2 * 4
        else:
            params += n * dtype_bytes

    cb = cache_dtype_bytes or dtype_bytes
    kv = cfg.n_kv_layers * batch * seq * kv_token_values(cfg) * cb
    kv += batch * state_slot_bytes(cfg)  # a slot's state or rings, whole
    # dp is served as independent batcher REPLICAS over disjoint device
    # slices (mesh.dp_submeshes): each replica holds its own full-``batch``
    # cache, so per-DEVICE kv bytes do not divide by dp — only the kv-head
    # tp sharding (unless replicated) shrinks them
    kv //= kv_tp

    # workspace: logits [B, V] f32 (vocab sharded on tp) + activations
    # [B, T, d]-scale temporaries + collective buffers; a conservative pad
    work = batch * cfg.vocab_size * 4 // tp + 64 * 2**20
    total = params + kv + work
    return {"params": params, "kv_cache": kv, "workspace": work, "total": total}


def kv_pool_block_bytes(cfg: ModelConfig, block_tokens: int,
                        kv_quant: str | None = None, tp: int = 1) -> int:
    """Per-device bytes of ONE paged-KV pool block: K+V for ``block_tokens``
    positions across every layer. Under int8 KVQ the codes are 1 byte/elem
    plus one f32 scale per (layer, kv-head, position). ``tp`` is the factor
    actually sharding the KV-head axis (1 under the replicated-KV GQA
    fallback) — the registry prices the whole pool as blocks x this."""
    quant = (kv_quant if kv_quant is not None else cfg.kv_quant) == "int8"
    dtype_bytes = 4 if cfg.dtype == "float32" else 2
    if cfg.is_mla:  # never quantized, never split (models/mla_moe.make_cache)
        return cfg.n_layers * block_tokens * kv_token_values(cfg) * dtype_bytes
    per_pos = (
        cfg.head_dim * (1 if quant else dtype_bytes) + (4 if quant else 0)
    )
    return 2 * cfg.n_kv_layers * cfg.n_kv_heads * block_tokens * per_pos // max(1, tp)
