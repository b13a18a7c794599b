"""Model hyperparameters, readable from GGUF metadata.

Key names follow the public GGUF conventions (``<arch>.block_count`` etc.)
that conversion tools write; ``from_gguf`` therefore loads any
llama/granite/mixtral-family file without sidecar config.

Families, by what the metadata carries (README.md, "Model families, and what
each refuses", has the causes each refusal gives):

* ``llama``, ``granite`` (four multipliers), ``qwen2`` (q/k/v biases),
  ``gemma`` (GeGLU, scaled tied embedding), any arch with ``expert_count``
  (Mixtral-style softmax top-k experts): one stack, ``models/llama.py``.
* ``attention.kv_lora_rank`` present (written by ``models/export.py`` for
  ``is_mla`` configs): latent attention with YaRN, leading dense layers then
  sigmoid-routed experts beside shared ones, residual streams:
  ``models/mla_moe.py``. Served on the paged pool of one chip only; int8 KV,
  the KV tiers, KVX1 export and a real GGUF's tensors are refused.
* ``layer_types`` present (``<arch>.attention.head_count_kv`` a list, 0 for
  a layer that keeps a recurrent state): Mamba-2 state-space layers beside
  grouped-query attention layers without rotary embedding
  (``granitehybrid``): ``models/ssm_hybrid.py``. Served on the paged pool of
  one chip, with a per-slot state pool beside it; the prefix cache,
  speculation, int8 KV, the KV tiers, KVX1 export and a real GGUF's tensors
  are refused. With ``<arch>.feed_forward_length`` a list as well (a width
  at the layers that are an FFN alone, 0 elsewhere: ``nemotron_h_moe``) a
  layer is ONE sublayer: a mixer, or routed two-matrix relu^2 experts in a
  latent (``models/experts.py``) of which the chip may hold a share.
* ``attention.sliding_window`` present with ``attention.head_count`` a list
  (one entry a layer): window-attention layers beside full-attention layers
  with their own head count and rotary table, a sigmoid gate a head on the
  attention output, a leading dense layer then sigmoid-routed experts beside
  a shared one (``laguna``): ``models/swa_moe.py``. Served on the paged pool
  of one chip: the pool holds the full layers' KV, a per-slot ring of
  ``window`` tokens beside it the window layers'; the prefix cache,
  speculation, int8 KV, the KV tiers, KVX1 export, a mesh and a real GGUF's
  tensors are refused.
* ``linear_attention.*`` keys present (``models/export.py`` writes them, with
  ``attention.head_count_kv`` a list, 0 for a layer that keeps a recurrent
  state): gated-delta-rule linear-attention layers beside gated softmax
  attention layers with q/k norms and a partly rotated head, softmax-routed
  experts with a sigmoid-gated shared one in every layer, of which the
  chip may hold a strided share (``qwen3next``): ``models/gdn_moe.py``.
  Served on the paged pool of one chip with a per-slot state pool beside
  it; refused as for the state-space family.
* ``attention.sparse.*`` keys present (``models/export.py`` writes them, with
  ``attention.head_count_kv`` a list, 0 for a layer that keeps a recurrent
  state): Lightning linear-attention layers (a constant decay a head, rotary
  q and k, an output norm and a sigmoid output gate) beside block-sparse
  attention layers without rotary embedding that, past ``dense_len`` keys,
  attend over ``topk`` picked blocks of keys a kv head, a dense SwiGLU in
  every layer, muP scalings (``minicpm_sala``): ``models/sala.py``. Served on
  the paged pool of one chip with a per-slot state pool and a per-slot cache
  of pooled keys beside it; refused as for the state-space family.
* ``gemma2``, ``gemma3``, ``qwen2moe``: rejected here (post-norms,
  soft-capping, a softmax-gated shared expert).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field, replace
from typing import Any


@dataclass(frozen=True)
class ModelConfig:
    arch: str = "llama"
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 128
    d_ff: int = 14336
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    max_seq_len: int = 8192
    tie_embeddings: bool = False
    # MoE (Mixtral-style); 0 experts = dense
    n_experts: int = 0
    n_experts_used: int = 0
    # Granite-3.x multipliers (all 1.0 / None for llama)
    embedding_scale: float = 1.0
    residual_scale: float = 1.0
    attention_scale: float | None = None  # None -> 1/sqrt(head_dim)
    logit_scale: float = 1.0
    # Qwen2-family: QKV projections carry biases
    attn_bias: bool = False
    # Gemma-family: GELU MLP and RMSNorm computing x * (1 + w); "relu2": an
    # MLP of TWO matrices, relu(x W_up)^2 W_down (nemotron_h: no gate matrix)
    mlp_act: str = "silu"  # "silu" | "gelu" | "relu2"
    norm_plus_one: bool = False
    dtype: str = "bfloat16"  # compute/weight dtype name (tests use float32)
    # KV cache storage: "none" (cache in `dtype`) or "int8" (codes + per-
    # position-per-head scales, ops/kvcache.py — halves decode's cache
    # traffic and capacity, unlocking larger serving batches)
    kv_quant: str = "none"
    # Pallas flash-attention for prefill (requires prefill at start_pos 0,
    # which the engine guarantees); decode keeps the fused XLA path
    use_flash_attention: bool = False
    # MoE dispatch: routed (sparse scatter/gather + optional ep shard_map,
    # parallel/moe.py) vs dense reference (every expert computes every token)
    use_routed_moe: bool = False
    moe_capacity_factor: float = 2.0
    # Unroll the decode-step layer loop (t == 1) instead of lax.scan: every
    # layer/cache index becomes static, so XLA reads each cache slab as a
    # view — no dynamic-slice materialization, no per-layer kernel-launch
    # overhead (a pallas_call costs ~93 us on the serving chip; 40 layers of
    # that is most of a decode step). Costs ~n_layers x compile time for the
    # decode program only; prefill keeps the scan.
    decode_unroll: bool = False
    # -- latent (MLA) attention; kv_lora_rank == 0 = plain GQA ----------------
    # q and kv go through a low-rank pair with a norm between (q_lora_rank,
    # kv_lora_rank); a head's key is [nope | rope] wide, the rope part ONE key
    # shared by all heads; the cache holds the normalised latent and the
    # rotated shared key (models/mla_moe.py). head_dim is nope + rope there.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN rotary scaling (factor 1 = plain rope)
    rope_factor: float = 1.0
    rope_orig_ctx: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    # -- sigmoid-routed experts beside shared ones (DeepSeek-V3 style) --------
    # n_dense_layers leading layers keep a dense SwiGLU of width d_ff; the
    # others route n_experts_used of n_experts experts of width moe_d_ff by
    # sigmoid score + a selection bias, and add n_shared_experts always-on
    # experts. Dropless: moe_capacity_factor is never read for this family.
    moe_d_ff: int = 0
    n_shared_experts: int = 0
    n_dense_layers: int = 0
    router_scoring: str = "softmax"  # "softmax" (Mixtral) | "sigmoid"
    routed_scaling: float = 1.0
    # -- multi-stream residual (mHC); 1 = the plain residual ------------------
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp_min: float = -10.0
    hc_res_clamp_max: float = 10.0
    # -- state-space (Mamba-2) layers beside attention layers -----------------
    # layer_types names every layer "mamba" or "attention" (empty = all
    # attention); a mamba layer keeps a state [ssm_n_heads, ssm_head_dim,
    # ssm_d_state] and the convolution's last inputs in place of KV, so only
    # the attention layers hold KV (n_kv_layers). use_rope False = no
    # positional embedding at all (NoPE): the state layers carry order.
    # A third kind, "experts", makes every layer ONE sublayer (nemotron_h): a
    # mixer without an MLP behind it, or the routed experts of
    # models/experts.py alone, which work in a latent of ``moe_latent``
    # columns between one down- and one up-projection a layer (0 = at
    # d_model); ssm_n_groups > 1: B and C a group of heads, the gated norm a
    # group of channels.
    layer_types: tuple[str, ...] = ()
    moe_latent: int = 0
    ssm_n_heads: int = 0
    ssm_head_dim: int = 0
    ssm_d_state: int = 0
    ssm_n_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 256
    use_rope: bool = True
    # -- window attention beside full attention (models/swa_moe.py) ----------
    # layer_types names every layer "full" or "window". A window layer sees
    # the last ``window`` keys, its own among them, and keeps them in a ring a
    # slot beside the pool, so only the full layers hold paged KV; it has its
    # own head count (n_heads is the full layers') and its own plain rotary
    # table over ``win_rope_dim`` dims of a head. A full layer rotates the
    # first ``rope_dim`` dims (0 = all) with the YaRN fields above, cos and sin
    # times ``rope_attn_factor``. attn_gate: a sigmoid gate a head, from the
    # layer's normed input, on the attention output.
    window: int = 0
    win_n_heads: int = 0
    win_rope_theta: float = 10000.0
    win_rope_dim: int = 0
    rope_dim: int = 0
    rope_attn_factor: float = 1.0
    attn_gate: bool = False
    # -- gated-delta-rule linear attention beside gated attention -------------
    # (models/gdn_moe.py) layer_types names every layer "linear" or
    # "attention". A linear layer keeps, a slot, a float32 state [lin_v_heads,
    # lin_k_dim, lin_v_dim] and the convolution's last ``ssm_conv`` raw inputs
    # over its q, k and v channels; lin_k_heads key heads serve lin_v_heads
    # value heads. An attention layer's wq also makes an elementwise sigmoid
    # gate on the attention output (attn_out_gate), q and k are normalised a
    # head (qk_norm) and the first ``rope_dim`` dims of a head are rotated.
    # Every layer's FFN is the routed form of models/experts.py with a softmax
    # router and a sigmoid gate on the shared expert (shared_gate). The norms'
    # published gains are zero-centred, x (1 + w): the loader folds 1 + w into
    # the leaf, so the program multiplies by a plain gain.
    lin_k_heads: int = 0
    lin_v_heads: int = 0
    lin_k_dim: int = 0
    lin_v_dim: int = 0
    attn_out_gate: bool = False
    qk_norm: bool = False
    shared_gate: bool = False
    # the chips that share a layer's experts and this chip's rank among them:
    # expert e lives on chip e mod moe_ep_size at place e // moe_ep_size of
    # that chip's stacks. The router keeps n_experts outputs; a pick of an
    # expert that is not held here adds nothing here.
    moe_ep_size: int = 1
    moe_ep_rank: int = 0
    # -- Lightning linear attention beside block-sparse attention -------------
    # (models/sala.py) layer_types names every layer "lightning" or "sparse".
    # A lightning layer keeps, a slot, a float32 state [lin_v_heads, lin_k_dim,
    # lin_v_dim] (lin_k_heads == lin_v_heads) that decays by a constant of
    # (layer, head); q and k are normalised a head (qk_norm) and rotated over
    # the whole head. A sparse layer (no rotary: use_rope False; qk_norm;
    # attn_out_gate) attends plainly while a query sees at most
    # sparse_dense_len keys, and past that over sparse_topk blocks of
    # sparse_block keys a kv head: the first sparse_init_blocks, those that
    # meet the last sparse_window keys, and the best by the group's summed
    # softmax scores against pooled keys (the mean of sparse_kernel keys every
    # sparse_stride), kept a slot beside the pool. stage_first_layer /
    # stage_depth: where this file's layers lie in the published stack (a
    # pipeline stage; 0 / 0 = the whole model), which the initialiser's decay
    # table reads.
    sparse_kernel: int = 0
    sparse_stride: int = 0
    sparse_block: int = 0
    sparse_window: int = 0
    sparse_init_blocks: int = 0
    sparse_topk: int = 0
    sparse_dense_len: int = 0
    stage_first_layer: int = 0
    stage_depth: int = 0

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def n_ssm_layers(self) -> int:
        return sum(t == "mamba" for t in self.layer_types)

    @property
    def n_win_layers(self) -> int:
        return sum(t == "window" for t in self.layer_types)

    @property
    def n_lin_layers(self) -> int:
        """Layers that keep a linear-attention state a slot: the gated delta
        rule's ("linear") or Lightning's ("lightning")."""
        return sum(t in ("linear", "lightning") for t in self.layer_types)

    @property
    def is_sala(self) -> bool:
        """Lightning layers beside block-sparse attention (models/sala.py)."""
        return "lightning" in self.layer_types or "sparse" in self.layer_types

    @property
    def whole_prompt_prefill(self) -> bool:
        """Whether an idle engine may prefill a prompt over one chunk in ONE
        dispatch (the flash kernel bounds its scores). A block-sparse layer's
        masked prefill holds a chunk's queries against every pooled key and a
        turn of keys at once: a whole prompt of them does not fit, so the
        family prefills in chunks always."""
        return self.use_flash_attention and not self.is_sala

    @property
    def sparse_pooled_len(self) -> int:
        """Pooled keys a slot a kv head a sparse layer keeps: one every
        ``sparse_stride`` tokens of ``max_seq_len``."""
        return self.max_seq_len // self.sparse_stride

    @property
    def n_expert_only_layers(self) -> int:
        """Layers that are routed experts and nothing else (one sublayer a
        layer: models/ssm_hybrid.py)."""
        return sum(t == "experts" for t in self.layer_types)

    @property
    def n_kv_layers(self) -> int:
        """Layers that hold paged KV: the pool's layer axis (a state-space or
        linear-attention layer keeps a state, a window layer a ring, all by
        slot; a layer of experts alone keeps nothing)."""
        return (self.n_layers - self.n_ssm_layers - self.n_win_layers - self.n_lin_layers
                - self.n_expert_only_layers)

    @property
    def recurrent(self) -> bool:
        """Whether a slot keeps a recurrent state (Mamba-2's, the gated delta
        rule's or Lightning's) that a decode step advances in place."""
        return bool(self.n_ssm_layers or self.n_lin_layers)

    @property
    def slot_state(self) -> bool:
        """Whether a slot keeps something beside its KV blocks that no block
        table describes (``ops.kvcache.WithState``): a recurrent state, or
        the window layers' ring."""
        return bool(self.recurrent or self.n_win_layers)

    @property
    def lin_conv_dim(self) -> int:
        """Channels the linear layers' causal convolution runs over: q, k, v."""
        return 2 * self.lin_k_heads * self.lin_k_dim + self.lin_v_heads * self.lin_v_dim

    @property
    def n_experts_held(self) -> int:
        """Experts of a layer this chip's stacks hold (all without a share)."""
        return self.n_experts // self.moe_ep_size

    @property
    def ssm_d_inner(self) -> int:
        return self.ssm_n_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels the causal convolution runs over: x, B and C."""
        return self.ssm_d_inner + 2 * self.ssm_n_groups * self.ssm_d_state

    @property
    def kv_pack(self) -> int:
        """kv heads laid side by side in one 128-lane cache row (state-space
        family only): a head of 64 would be padded to 128 lanes by the device
        and is not a row the paged decode kernel can copy, two of them are."""
        d = self.head_dim
        if self.n_ssm_layers and d < 128 and 128 % d == 0 and self.n_kv_heads % (128 // d) == 0:
            return 128 // d
        return 1

    @property
    def family(self) -> str:
        """The model file that runs this configuration (models/<family>.py)."""
        if self.n_ssm_layers:
            return "ssm_hybrid"
        if self.n_win_layers:
            return "swa_moe"
        if self.is_sala:
            return "sala"
        if self.n_lin_layers:
            return "gdn_moe"
        return "mla_moe" if self.is_mla else "llama"

    @property
    def n_moe_layers(self) -> int:
        """Layers whose FFN is the routed-expert form of ``models/experts.py``
        (the latent-attention, window-attention and linear-attention
        families, and the state-space family's layers of experts alone: the
        Mixtral family routes in every layer and keeps no dense stack)."""
        if self.n_expert_only_layers:
            return self.n_expert_only_layers
        routed = (self.is_mla or self.n_win_layers or self.n_lin_layers) and self.is_moe
        return self.n_layers - self.n_dense_layers if routed else 0

    @property
    def rope_mscale_sq(self) -> float:
        """YaRN's softmax-scale correction, squared (both q and k carry it)."""
        if self.rope_factor <= 1.0 or not self.rope_mscale_all_dim:
            return 1.0
        m = 0.1 * self.rope_mscale_all_dim * math.log(self.rope_factor) + 1.0
        return m * m

    @property
    def attn_scale(self) -> float:
        if self.attention_scale is not None:
            return self.attention_scale
        return self.head_dim**-0.5 * self.rope_mscale_sq

    def kv_cache_dims(self) -> tuple[tuple[int, int], tuple[int, int]]:
        """(heads, width) of the two caches a token a layer: K and V alike
        for GQA; for MLA the normalised latent and the rotated shared key
        (zero-padded to the lane tile), one "head" each (ops/kvcache.py
        treats the pair as opaque)."""
        if self.is_mla:
            # the rotary key's rows are padded to whole 128-lane tiles: the
            # device lays a narrower minor plane out that wide anyway, and the
            # decode kernel can only copy whole tiles out of the pool
            return (1, self.kv_lora_rank), (1, -(-self.qk_rope_head_dim // 128) * 128)
        if self.kv_pack > 1:
            packed = (self.n_kv_heads // self.kv_pack, self.head_dim * self.kv_pack)
            return packed, packed
        return (self.n_kv_heads, self.head_dim), (self.n_kv_heads, self.head_dim)

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    def with_(self, **kw: Any) -> "ModelConfig":
        return replace(self, **kw)

    # -- construction --------------------------------------------------------

    @classmethod
    def from_gguf_metadata(cls, md: dict[str, Any]) -> "ModelConfig":
        arch = str(md.get("general.architecture", "llama"))

        def g(key: str, default: Any = None) -> Any:
            return md.get(f"{arch}.{key}", default)

        heads = g("attention.head_count", 32)
        if hasattr(heads, "tolist"):
            heads = heads.tolist()
        # a list: one entry a layer (window layers have their own count)
        heads_by_layer = [int(h) for h in heads] if isinstance(heads, (list, tuple)) else None
        n_heads = heads_by_layer[0] if heads_by_layer else int(heads)
        kv_heads = g("attention.head_count_kv", n_heads)
        if hasattr(kv_heads, "tolist"):
            kv_heads = kv_heads.tolist()
        # a list: one entry a layer, 0 for a layer that keeps no KV
        kv_by_layer = [int(h) for h in kv_heads] if isinstance(kv_heads, (list, tuple)) else None
        d_model = int(g("embedding_length", 4096))
        d_ff = g("feed_forward_length", 4 * d_model)
        if hasattr(d_ff, "tolist"):
            d_ff = d_ff.tolist()
        # a list: one entry a layer, 0 for a layer that is no FFN
        ff_by_layer = [int(f) for f in d_ff] if isinstance(d_ff, (list, tuple)) else None
        head_dim = int(g("attention.key_length", d_model // n_heads))
        vocab = md.get(f"{arch}.vocab_size")
        if vocab is None:
            toks = md.get("tokenizer.ggml.tokens")
            vocab = len(toks) if toks is not None else 32000
        # architecture-family quirks beyond the metadata keys (the same
        # special-casing llama.cpp's build_* graph constructors apply).
        # Families whose topology this model does NOT implement are rejected
        # loudly — half-running them (dropped shared experts, missing
        # post-norms/softcapping) would load fine and produce garbage.
        if arch in ("gemma2", "gemma3", "qwen2moe"):
            raise NotImplementedError(
                f"architecture {arch!r} needs topology this model does not "
                "implement (post-norms/softcapping; qwen2moe's softmax-gated "
                "shared expert)"
            )
        family: dict[str, Any] = {}
        if arch == "qwen2":
            family["attn_bias"] = True
        elif arch == "gemma":
            # NOTE: no norm_plus_one here — llama.cpp's GGUF converter folds
            # gemma's (1+w) into the stored norm weights, so GGUF-loaded
            # models use the plain multiply. The flag exists for checkpoints
            # that keep the HF convention.
            family |= {
                "mlp_act": "gelu",
                "tie_embeddings": True,
                # gemma scales embeddings by sqrt(d_model)
                "embedding_scale": float(d_model) ** 0.5,
            }
        kwargs: dict[str, Any] = dict(
            arch=arch,
            vocab_size=int(vocab),
            d_model=d_model,
            n_layers=int(g("block_count", 32)),
            n_heads=n_heads,
            n_kv_heads=max(kv_by_layer) if kv_by_layer else int(kv_heads),
            head_dim=head_dim,
            d_ff=max(ff_by_layer) if ff_by_layer else int(d_ff),
            rope_theta=float(g("rope.freq_base", 10000.0)),
            rms_eps=float(g("attention.layer_norm_rms_epsilon", 1e-5)),
            max_seq_len=int(g("context_length", 8192)),
            n_experts=int(g("expert_count", 0) or 0),
            n_experts_used=int(g("expert_used_count", 0) or 0),
            embedding_scale=float(g("embedding_scale", 1.0)),
            residual_scale=float(g("residual_scale", 1.0)),
            attention_scale=(
                float(g("attention.scale")) if g("attention.scale") is not None else None
            ),
            # GGUF stores granite's logit scale as a divisor (engines multiply
            # final logits by 1/f_logit_scale); internally we keep a multiplier
            logit_scale=1.0 / float(g("logit_scale", 1.0)),
        )
        if g("attention.kv_lora_rank") is not None:
            # latent attention + sigmoid experts + residual streams: the keys
            # models/export.config_metadata writes for the family
            nope = int(g("attention.key_length_nope", head_dim))
            rope = int(g("rope.dimension_count", 0))
            family |= dict(
                q_lora_rank=int(g("attention.q_lora_rank", 0)),
                kv_lora_rank=int(g("attention.kv_lora_rank")),
                qk_nope_head_dim=nope,
                qk_rope_head_dim=rope,
                v_head_dim=int(g("attention.value_length", head_dim)),
                head_dim=nope + rope,
                rope_factor=float(g("rope.scaling.factor", 1.0)),
                rope_orig_ctx=int(g("rope.scaling.original_context_length", 0)),
                rope_beta_fast=float(g("rope.scaling.yarn_beta_fast", 32.0)),
                rope_beta_slow=float(g("rope.scaling.yarn_beta_slow", 1.0)),
                rope_mscale=float(g("rope.scaling.yarn_mscale", 1.0)),
                rope_mscale_all_dim=float(g("rope.scaling.yarn_mscale_all_dim", 0.0)),
                moe_d_ff=int(g("expert_feed_forward_length", 0)),
                n_shared_experts=int(g("expert_shared_count", 0)),
                n_dense_layers=int(g("leading_dense_block_count", 0)),
                router_scoring="sigmoid" if int(g("expert_gating_func", 1)) == 2 else "softmax",
                routed_scaling=float(g("expert_weights_scale", 1.0)),
                hc_mult=int(g("hyper_connection.count", 1)),
                hc_sinkhorn_iters=int(g("hyper_connection.sinkhorn_iterations", 20)),
                hc_eps=float(g("hyper_connection.epsilon", 1e-6)),
                hc_res_clamp_min=float(g("hyper_connection.res_clamp_min", -10.0)),
                hc_res_clamp_max=float(g("hyper_connection.res_clamp_max", 10.0)),
            )
        if g("ssm.state_size") is not None and kv_by_layer:
            # state-space layers beside attention: a layer with no kv head
            # keeps a state (the keys llama.cpp's granitehybrid writes)
            heads = int(g("ssm.time_step_rank"))
            family |= dict(
                layer_types=tuple("attention" if h else "mamba" for h in kv_by_layer),
                ssm_n_heads=heads,
                ssm_head_dim=int(g("ssm.inner_size")) // heads,
                ssm_d_state=int(g("ssm.state_size")),
                ssm_n_groups=int(g("ssm.group_count", 1)),
                ssm_conv=int(g("ssm.conv_kernel", 4)),
                ssm_chunk=int(g("ssm.chunk_size", 256)),
                use_rope=bool(g("rope.scaling.finetuned", False)),
            )
            if ff_by_layer:
                # one sublayer a layer (llama.cpp's nemotron_h_moe: a layer
                # with neither a kv head nor an FFN width is recurrent), the
                # FFN layers two-matrix relu^2 experts in a latent
                fe = int(g("expert_feed_forward_length", 0))
                family |= dict(
                    layer_types=tuple("attention" if h else "experts" if f else "mamba"
                                      for h, f in zip(kv_by_layer, ff_by_layer)),
                    mlp_act="relu2",
                    moe_d_ff=fe,
                    n_shared_experts=int(g("expert_shared_feed_forward_length", 0)) // max(1, fe),
                    moe_latent=int(g("moe_latent_size", 0)),
                    router_scoring="sigmoid" if int(g("expert_gating_func", 2)) == 2 else "softmax",
                    routed_scaling=float(g("expert_weights_scale", 1.0)),
                    moe_ep_size=int(g("expert_parallel.count", 1)),
                    moe_ep_rank=int(g("expert_parallel.rank", 0)),
                )
        if g("attention.sliding_window") is not None and heads_by_layer:
            # window layers beside full layers, experts after a leading dense
            # layer: the keys models/export.config_metadata writes
            is_win = [bool(x) for x in g("attention.sliding_window_pattern")]
            family |= dict(
                layer_types=tuple("window" if w else "full" for w in is_win),
                n_heads=next(h for h, w in zip(heads_by_layer, is_win) if not w),
                win_n_heads=next(h for h, w in zip(heads_by_layer, is_win) if w),
                window=int(g("attention.sliding_window")),
                win_rope_theta=float(g("rope.freq_base_swa", 10000.0)),
                win_rope_dim=int(g("rope.dimension_count_swa", 0)),
                rope_dim=int(g("rope.dimension_count", 0)),
                rope_factor=float(g("rope.scaling.factor", 1.0)),
                rope_orig_ctx=int(g("rope.scaling.original_context_length", 0)),
                rope_beta_fast=float(g("rope.scaling.yarn_beta_fast", 32.0)),
                rope_beta_slow=float(g("rope.scaling.yarn_beta_slow", 1.0)),
                rope_attn_factor=float(g("rope.scaling.attn_factor", 1.0)),
                attn_gate=bool(g("attention.output_gate", False)),
                moe_d_ff=int(g("expert_feed_forward_length", 0)),
                n_shared_experts=int(g("expert_shared_count", 0)),
                n_dense_layers=int(g("leading_dense_block_count", 0)),
                router_scoring="sigmoid" if int(g("expert_gating_func", 1)) == 2 else "softmax",
                routed_scaling=float(g("expert_weights_scale", 1.0)),
            )
        if g("attention.sparse.block_size") is not None and kv_by_layer:
            # Lightning layers beside block-sparse attention, a dense SwiGLU
            # in every layer: the keys models/export.config_metadata writes.
            # The family runs the published combination of switches only
            for key, want in (("attention.qk_norm", True), ("attention.output_gate", True),
                              ("attention.use_rope", False), ("linear_attention.use_rope", True),
                              ("linear_attention.output_gate", True),
                              ("linear_attention.output_norm", True)):
                if bool(g(key, want)) != want:
                    raise NotImplementedError(
                        f"{arch}: {key} = {g(key)!r} is not the published model's "
                        f"({want}), and models/sala.py computes that one only")
            family |= dict(
                layer_types=tuple("sparse" if h else "lightning" for h in kv_by_layer),
                lin_k_heads=int(g("linear_attention.key_head_count")),
                lin_v_heads=int(g("linear_attention.value_head_count")),
                lin_k_dim=int(g("linear_attention.key_length")),
                lin_v_dim=int(g("linear_attention.value_length")),
                attn_out_gate=True, qk_norm=True, use_rope=False,
                sparse_kernel=int(g("attention.sparse.kernel_size")),
                sparse_stride=int(g("attention.sparse.kernel_stride")),
                sparse_block=int(g("attention.sparse.block_size")),
                sparse_window=int(g("attention.sparse.window_size")),
                sparse_init_blocks=int(g("attention.sparse.init_blocks")),
                sparse_topk=int(g("attention.sparse.topk")),
                sparse_dense_len=int(g("attention.sparse.dense_len")),
                stage_first_layer=int(g("pipeline.first_layer", 0)),
                stage_depth=int(g("pipeline.depth", 0)),
            )
        elif g("linear_attention.key_head_count") is not None and kv_by_layer:
            # gated-delta-rule layers beside gated attention, experts in every
            # layer: the keys models/export.config_metadata writes
            family |= dict(
                layer_types=tuple("attention" if h else "linear" for h in kv_by_layer),
                lin_k_heads=int(g("linear_attention.key_head_count")),
                lin_v_heads=int(g("linear_attention.value_head_count")),
                lin_k_dim=int(g("linear_attention.key_length")),
                lin_v_dim=int(g("linear_attention.value_length")),
                ssm_conv=int(g("linear_attention.conv_kernel", 4)),
                rope_dim=int(g("rope.dimension_count", 0)),
                attn_out_gate=bool(g("attention.output_gate", False)),
                qk_norm=bool(g("attention.qk_norm", False)),
                moe_d_ff=int(g("expert_feed_forward_length", 0)),
                n_shared_experts=int(g("expert_shared_count", 0)),
                shared_gate=bool(g("expert_shared_gate", False)),
                router_scoring="sigmoid" if int(g("expert_gating_func", 1)) == 2 else "softmax",
                routed_scaling=float(g("expert_weights_scale", 1.0)),
                moe_ep_size=int(g("expert_parallel.count", 1)),
                moe_ep_rank=int(g("expert_parallel.rank", 0)),
            )
        kwargs.update(family)  # family quirks win over absent metadata keys
        return cls(**kwargs)

    @classmethod
    def tiny(cls, **kw: Any) -> "ModelConfig":
        """A 4-layer toy config for CPU tests."""
        base = dict(
            vocab_size=512,
            d_model=64,
            n_layers=4,
            n_heads=4,
            n_kv_heads=2,
            head_dim=16,
            d_ff=128,
            max_seq_len=256,
            dtype="float32",
        )
        base.update(kw)
        return cls(**base)
