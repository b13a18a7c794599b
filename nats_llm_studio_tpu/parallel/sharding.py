"""NamedSharding rules for the stacked-params pytree.

Megatron-style TP (BASELINE.md config 3: Llama-3-70B TP=8 on v5e-8): QKV and
FFN-in sharded on their output-features axis, attn-out and FFN-down on their
input axis — so each block does local matmuls and GSPMD inserts exactly one
all-reduce after attention and one after the MLP. Experts shard on the ep
axis (config 4: Mixtral). The KV cache shards heads on tp, batch on dp, and
the sequence axis on sp (ring attention; SURVEY.md §5).

Weights keep a leading [L] stack axis (lax.scan), so every rule below starts
with None for L.
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.config import ModelConfig
from ..ops.wquant import QTensor, QTensor4
from .mesh import AXIS_DP, AXIS_EP, AXIS_PP, AXIS_SP, AXIS_TP


def _axis(mesh: Mesh, name: str) -> str | None:
    """Use an axis only if the mesh has it with size > 1."""
    return name if name in mesh.axis_names and mesh.shape[name] > 1 else None


def kv_replicated(mesh: Mesh, cfg: ModelConfig) -> bool:
    """True when the GQA replicated-KV fallback is active: tp exceeds the
    KV head count (so the cache heads axis cannot shard) but still divides
    the query heads — wq/wo and the FFN shard normally while wk/wv and the
    KV cache stay replicated. Small KV trees make this a good trade: a
    Llama-3-8B's 8 KV heads on a tp=16 pod replicate ~1/9 of the weight
    bytes to keep 16-way sharding on the other 8/9."""
    tp = mesh.shape.get(AXIS_TP, 1)
    return tp > 1 and cfg.n_kv_heads % tp != 0 and tp > cfg.n_kv_heads \
        and cfg.n_heads % tp == 0


def param_sharding_rules(mesh: Mesh, cfg: ModelConfig | None = None) -> dict[str, P]:
    """PartitionSpec per params-pytree key (blocks.* keys are the stacked
    per-layer weights). The leading [L] stack axis shards on pp (pipeline
    stages own contiguous layer slices — parallel/pipeline.py).

    With ``cfg``, GQA models whose KV head count tp cannot divide get the
    replicated-KV fallback (``kv_replicated``): wk/wv/bk/bv stay whole per
    chip so the KV cache's heads axis can too."""
    tp = _axis(mesh, AXIS_TP)
    ep = _axis(mesh, AXIS_EP)
    pp = _axis(mesh, AXIS_PP)
    kv = None if cfg is not None and kv_replicated(mesh, cfg) else tp
    if cfg is not None and cfg.is_mla:
        return _mla_rules(cfg, ep, pp)
    if cfg is not None and cfg.n_ssm_layers:
        return _ssm_rules(pp)
    if cfg is not None and cfg.n_win_layers:
        return _swa_rules(ep, pp)
    if cfg is not None and cfg.is_sala:
        return _sala_rules(pp)
    if cfg is not None and cfg.n_lin_layers:
        return _gdn_rules(pp)
    return {
        "embed": P(None, None),  # replicated: read once per token, cheap
        "out_norm": P(None),
        "lm_head": P(None, tp),  # vocab-sharded logits; argmax/sample gathers
        "blocks.attn_norm": P(pp, None),
        "blocks.ffn_norm": P(pp, None),
        "blocks.wq": P(pp, None, tp),
        "blocks.wk": P(pp, None, kv),
        "blocks.wv": P(pp, None, kv),
        "blocks.wo": P(pp, tp, None),
        "blocks.bq": P(pp, tp),  # qwen2 QKV biases: output-feature sharded
        "blocks.bk": P(pp, kv),
        "blocks.bv": P(pp, kv),
        "blocks.w_gate": P(pp, None, tp),
        "blocks.w_up": P(pp, None, tp),
        "blocks.w_down": P(pp, tp, None),
        "blocks.router": P(pp, None, None),
        "blocks.w_gate_e": P(pp, ep, None, tp),
        "blocks.w_up_e": P(pp, ep, None, tp),
        "blocks.w_down_e": P(pp, ep, tp, None),
    }


def _mla_rules(cfg: ModelConfig, ep, pp) -> dict[str, P]:
    """A rule for every leaf ``models.mla_moe.init_params`` makes for ``cfg``
    (the query's pair or its one matrix, mixers only with streams): the two
    stacks' layer axis on pp, the routed experts on ep, everything else whole
    (the family is served on one chip a replica: ``validate_mesh_for_config``
    refuses a tp split of the latent cache)."""
    rank = {"attn_norm": 1, "ffn_norm": 1, "kv_norm": 1, "w_dkv": 2, "w_ukv": 2, "wo": 2}
    rank |= {"q_norm": 1, "w_dq": 2, "w_uq": 2} if cfg.q_lora_rank else {"wq": 2}
    if cfg.hc_mult > 1:
        rank |= {"hc_attn_w": 2, "hc_attn_a": 1, "hc_attn_b": 1,
                 "hc_ffn_w": 2, "hc_ffn_a": 1, "hc_ffn_b": 1}
    dense = rank | {"w_gate": 2, "w_up": 2, "w_down": 2}
    moe = rank | {"router": 2, "e_bias": 1, "w_gate_s": 2, "w_up_s": 2, "w_down_s": 2}
    rules = {"embed": P(None, None), "out_norm": P(None), "lm_head": P(None, None)}
    rules |= {f"blocks.dense.{k}": P(pp, *[None] * r) for k, r in dense.items()}
    rules |= {f"blocks.moe.{k}": P(pp, *[None] * r) for k, r in moe.items()}
    rules |= {f"blocks.moe.{k}": P(pp, ep, None, None)
              for k in ("w_gate_e", "w_up_e", "w_down_e")}
    return rules


def _ssm_rules(pp) -> dict[str, P]:
    """A rule for every leaf ``models.ssm_hybrid.init_params`` makes: the
    stacks' layer axis on pp, everything else whole (the family is served on
    one chip a replica: ``validate_mesh_for_config`` refuses a mesh over it;
    the experts a chip holds of a layer are the model's own, as in
    ``_gdn_rules``)."""
    ffn = {"mix_norm": 1, "ffn_norm": 1, "w_gate": 2, "w_up": 2, "w_down": 2}
    mamba = ffn | {"w_in": 2, "w_dt": 2, "conv_w": 2, "conv_b": 1, "dt_bias": 1, "a_log": 1,
                   "d_skip": 1, "gate_norm": 1, "w_out": 2}
    attn = ffn | {"wq": 2, "wk": 2, "wv": 2, "wo": 2}
    rules = {"embed": P(None, None), "out_norm": P(None), "lm_head": P(None, None)}
    rules |= {f"blocks.mamba.{k}": P(pp, *[None] * r) for k, r in mamba.items()}
    rules |= {f"blocks.attn.{k}": P(pp, *[None] * r) for k, r in attn.items()}
    # a layer of experts alone: two-matrix experts in a latent
    moe = {"mix_norm": 1, "router": 2, "e_bias": 1, "w_lat_down": 2, "w_lat_up": 2,
           "w_up_e": 3, "w_down_e": 3, "w_up_s": 2, "w_down_s": 2}
    rules |= {f"blocks.moe.{k}": P(pp, *[None] * r) for k, r in moe.items()}
    return rules


def _swa_rules(ep, pp) -> dict[str, P]:
    """A rule for every leaf ``models.swa_moe.init_params`` makes: the four
    stacks' layer axis on pp, the routed experts on ep, everything else whole
    (the family is served on one chip a replica: ``validate_mesh_for_config``
    refuses a mesh over it)."""
    attn = {"attn_norm": 1, "wq": 2, "wk": 2, "wv": 2, "wg": 2, "wo": 2}
    dense = {"ffn_norm": 1, "w_gate": 2, "w_up": 2, "w_down": 2}
    moe = {"ffn_norm": 1, "router": 2, "e_bias": 1, "w_gate_s": 2, "w_up_s": 2, "w_down_s": 2}
    rules = {"embed": P(None, None), "out_norm": P(None), "lm_head": P(None, None)}
    for name, leaves in (("full", attn), ("win", attn), ("dense", dense), ("moe", moe)):
        rules |= {f"blocks.{name}.{k}": P(pp, *[None] * r) for k, r in leaves.items()}
    rules |= {f"blocks.moe.{k}": P(pp, ep, None, None)
              for k in ("w_gate_e", "w_up_e", "w_down_e")}
    return rules


def _gdn_rules(pp) -> dict[str, P]:
    """A rule for every leaf ``models.gdn_moe.init_params`` makes: the three
    stacks' layer axis on pp, everything else whole. The experts a chip holds
    of a layer are the model's own (``moe_ep_size`` / ``moe_ep_rank`` in its
    header), not a mesh axis: the family is served on one chip a replica
    (``validate_mesh_for_config`` refuses a mesh over it)."""
    linear = {"mix_norm": 1, "w_qkvz": 2, "w_ba": 2, "conv_w": 2, "dt_bias": 1, "a_log": 1,
              "gate_norm": 1, "w_out": 2}
    attn = {"mix_norm": 1, "wq": 2, "wk": 2, "wv": 2, "wo": 2, "q_norm": 1, "k_norm": 1}
    moe = {"ffn_norm": 1, "router": 2, "e_bias": 1, "shared_gate": 1, "w_gate_e": 3,
           "w_up_e": 3, "w_down_e": 3, "w_gate_s": 2, "w_up_s": 2, "w_down_s": 2}
    rules = {"embed": P(None, None), "out_norm": P(None), "lm_head": P(None, None)}
    for name, leaves in (("linear", linear), ("attn", attn), ("moe", moe)):
        rules |= {f"blocks.{name}.{k}": P(pp, *[None] * r) for k, r in leaves.items()}
    return rules


def _sala_rules(pp) -> dict[str, P]:
    """A rule for every leaf ``models.sala.init_params`` makes: the two
    stacks' layer axis on pp, everything else whole (the family is served on
    one chip a replica: ``validate_mesh_for_config`` refuses a mesh over it)."""
    ffn = {"mix_norm": 1, "ffn_norm": 1, "w_gate": 2, "w_up": 2, "w_down": 2}
    linear = ffn | {"wq": 2, "wk": 2, "wv": 2, "wg": 2, "wo": 2, "q_norm": 1, "k_norm": 1,
                    "out_norm": 1, "decay": 1}
    attn = ffn | {"wq": 2, "wk": 2, "wv": 2, "wo": 2, "q_norm": 1, "k_norm": 1}
    rules = {"embed": P(None, None), "out_norm": P(None), "lm_head": P(None, None)}
    for name, leaves in (("linear", linear), ("attn", attn)):
        rules |= {f"blocks.{name}.{k}": P(pp, *[None] * r) for k, r in leaves.items()}
    return rules


def scale_spec(weight_spec: P) -> P:
    """Spec for a QTensor's per-output-channel scale [..., 1, out]: same as
    the weight's but with the contraction (second-to-last) axis unsharded —
    the scale has extent 1 there."""
    parts = list(weight_spec) + [None] * (2 - len(weight_spec))
    parts[-2] = None
    return P(*parts)


def _flatten_keys(params: dict[str, Any], prefix: str = "") -> dict[str, Any]:
    out = {}
    for k, v in params.items():
        path = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(_flatten_keys(v, f"{path}."))
        else:
            out[path] = v
    return out


def shard_params(params: dict[str, Any], mesh: Mesh,
                 cfg: ModelConfig | None = None) -> dict[str, Any]:
    """device_put every leaf with its rule (replicated if no rule matches).

    For giant checkpoints prefer loading shard-by-shard (store/loader);
    this helper is for params already materialized on host. Pass ``cfg``
    to honor the replicated-KV GQA fallback (``kv_replicated``).
    """
    rules = param_sharding_rules(mesh, cfg)

    def place(path: str, leaf):
        spec = rules.get(path, P())
        if isinstance(leaf, QTensor):
            return QTensor(
                q=jax.device_put(leaf.q, NamedSharding(mesh, spec)),
                s=jax.device_put(leaf.s, NamedSharding(mesh, scale_spec(spec))),
            )
        if isinstance(leaf, QTensor4):
            # grouped int4: the packed codes [..., in/2, out] and the
            # per-group scale/zero [..., in/group, out] all keep the
            # weight's own spec — unlike the int8 scale (extent 1 on the
            # contraction axis), the grouped axis has real extent and
            # shards exactly as the contraction axis does
            sh = NamedSharding(mesh, spec)
            return QTensor4(
                q=jax.device_put(leaf.q, sh),
                s=jax.device_put(leaf.s, sh),
                z=jax.device_put(leaf.z, sh),
                group=leaf.group,
            )
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    def walk(node: dict[str, Any], prefix: str = "") -> dict[str, Any]:
        out = {}
        for k, v in node.items():
            path = f"{prefix}{k}"
            out[k] = walk(v, f"{path}.") if isinstance(v, dict) else place(path, v)
        return out

    return walk(params)


def cache_spec(mesh: Mesh, cfg: ModelConfig | None = None) -> P:
    """KV cache [B, L, Hkv, S, D]: batch on dp, layers on pp, heads on tp,
    sequence on sp (the ring-attention axis — long prompts' cache memory
    scales down with the sp degree; SURVEY.md §5 long-context). With
    ``cfg``, the heads axis drops tp under the replicated-KV GQA fallback
    (``kv_replicated``) — the cache must mirror wk/wv's sharding or every
    write would be a resharding collective."""
    tp = _axis(mesh, AXIS_TP)
    if cfg is not None and kv_replicated(mesh, cfg):
        tp = None
    return P(
        _axis(mesh, AXIS_DP), _axis(mesh, AXIS_PP), tp,
        _axis(mesh, AXIS_SP), None,
    )


def row_cache_spec(mesh: Mesh, cfg: ModelConfig | None = None) -> P:
    """Transient prefill row caches and prefix-cache blocks
    [m, L, Hkv, S', D]: heads on tp only. The batch axis is often 1 and S'
    a prompt bucket, so dp/sp cannot apply; pp never serves the dense
    path. Same KV-head rule as ``cache_spec`` so block copy-ins between a
    row cache and the serving ring never reshard."""
    tp = _axis(mesh, AXIS_TP)
    if cfg is not None and kv_replicated(mesh, cfg):
        tp = None
    return P(None, None, tp, None, None)


def pool_spec(mesh: Mesh, cfg: ModelConfig | None = None) -> P:
    """The paged KV block pool [NB, L, Hkv, T, D]: KV heads on tp, every
    other axis replicated. The block axis stays unsharded — block ids are
    global, so a gather of any slot's table lands on the device that owns
    the same head shard, and pool<->view moves never reshard. Same
    replicated-KV fallback rule as ``cache_spec``; the axis layout matches
    ``row_cache_spec`` (heads at index 2) by construction."""
    return row_cache_spec(mesh, cfg)


def shard_cache(k_cache, v_cache, mesh: Mesh, cfg: ModelConfig | None = None,
                spec: P | None = None):
    from ..ops.kvcache import KVQ, is_quantized

    if spec is None:
        spec = cache_spec(mesh, cfg)
    sh = NamedSharding(mesh, spec)
    # quantized caches: codes take the full cache spec, scales drop the
    # trailing head_dim axis
    sh_scale = NamedSharding(mesh, P(*list(spec)[:-1]))

    def put(c):
        if is_quantized(c):
            return KVQ(q=jax.device_put(c.q, sh), s=jax.device_put(c.s, sh_scale))
        return jax.device_put(c, sh)

    return put(k_cache), put(v_cache)


def batch_spec(mesh: Mesh) -> P:
    """Token/position arrays [B, ...]: batch on dp."""
    return P(_axis(mesh, AXIS_DP))


def validate_mesh_for_config(mesh: Mesh, cfg: ModelConfig,
                             allow_pp: bool = False) -> None:
    """Fail fast on indivisible shardings instead of cryptic XLA errors.

    ``allow_pp``: only callers that actually route through
    ``parallel.pipeline.pipeline_forward`` may accept a pp axis. The dense
    ``models.llama.forward`` over pp-sharded weights would not error — GSPMD
    would silently all-gather every layer's weights per step — so the
    serving path (default) rejects pp loudly instead."""
    if not allow_pp and mesh.shape.get(AXIS_PP, 1) > 1:
        raise ValueError(
            "mesh has a pp axis but this path runs the dense forward; "
            "pipeline parallelism is served by parallel.pipeline."
            "pipeline_forward (use tp/dp/sp/ep for the serving mesh)"
        )
    tp = mesh.shape.get(AXIS_TP, 1)
    ep = mesh.shape.get(AXIS_EP, 1)
    if cfg.is_mla and mesh.size > 1:
        raise ValueError(
            f"latent-attention models ({cfg.arch}) serve on one chip a replica "
            "(MESH_SHAPE=off): the latent cache has one kv head, so there is no "
            "tp split of it, and the dropless expert layer has no ep exchange yet"
        )
    if cfg.n_ssm_layers and mesh.size > 1:
        raise ValueError(
            f"state-space models ({cfg.arch}) serve on one chip a replica "
            "(MESH_SHAPE=off): the scan's heads and the per-slot state pool "
            "have no mesh split yet"
            + (", and the chip's share of a layer's latent experts is the model's own "
               "(expert_parallel.count / rank in its header), with no exchange"
               if cfg.n_moe_layers else "")
        )
    if cfg.is_sala and mesh.size > 1:
        raise ValueError(
            f"lightning / block-sparse models ({cfg.arch}) serve on one chip a replica "
            "(MESH_SHAPE=off): the per-slot state pool and the per-slot pooled keys "
            "have no mesh split yet, and the picked walk no tp split of the kv heads"
        )
    if cfg.n_lin_layers and mesh.size > 1:
        raise ValueError(
            f"linear-attention models ({cfg.arch}) serve on one chip a replica "
            "(MESH_SHAPE=off): the per-slot state pool has no mesh split yet, and "
            "the chip's share of a layer's experts is the model's own "
            "(expert_parallel.count / rank in its header), with no exchange"
        )
    if cfg.n_win_layers and mesh.size > 1:
        raise ValueError(
            f"window-attention models ({cfg.arch}) serve on one chip a replica "
            "(MESH_SHAPE=off): the per-slot ring and its kernel have no tp "
            "split of the kv heads yet, and the dropless expert layer no ep "
            "exchange"
        )
    # every message names the FULL axis factoring, not just the failing
    # axis — a multi-axis mesh ("dp=2,ep=2,tp=2") read back as bare "tp=2"
    # sends the operator hunting the wrong knob
    factoring = ",".join(f"{k}={v}" for k, v in dict(mesh.shape).items())
    where = f"unservable on this mesh ({factoring})"
    if cfg.n_heads % tp and tp > 1:
        raise ValueError(
            f"{where}: n_heads={cfg.n_heads} not divisible by tp={tp}"
        )
    if cfg.n_kv_heads % tp and tp > 1 and not kv_replicated(mesh, cfg):
        # tp > n_kv_heads with tp | n_heads is served via the replicated-KV
        # fallback (kv_replicated); anything else has no clean layout
        raise ValueError(
            f"{where}: n_kv_heads={cfg.n_kv_heads} not "
            f"divisible by tp={tp} (replicated-KV fallback needs "
            f"tp > n_kv_heads and tp | n_heads={cfg.n_heads})"
        )
    if cfg.d_ff % tp and tp > 1:
        raise ValueError(
            f"{where}: d_ff={cfg.d_ff} not divisible by tp={tp}"
        )
    if cfg.is_moe and ep > 1 and cfg.n_experts % ep:
        raise ValueError(
            f"{where}: n_experts={cfg.n_experts} not divisible by ep={ep}"
        )
    if ep > 1 and not cfg.is_moe:
        raise ValueError(
            f"{where}: mesh has an ep axis but the model is dense "
            f"(n_experts=0) — nothing shards on ep"
        )
    sp = mesh.shape.get(AXIS_SP, 1)
    if sp > 1 and cfg.max_seq_len % sp:
        raise ValueError(
            f"{where}: max_seq_len={cfg.max_seq_len} not divisible by sp={sp}"
        )
    pp = mesh.shape.get(AXIS_PP, 1)
    if pp > 1 and cfg.n_layers % pp:
        raise ValueError(
            f"{where}: n_layers={cfg.n_layers} not divisible by pp={pp}"
        )
