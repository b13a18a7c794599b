"""Export a params pytree to a GGUF file.

Round-trips with ``parallel.loader.load_params_sharded``: the fixture-creation path for
integration tests (SURVEY.md §4.1) and the conversion path for publishing
models into the Object Store bucket in the reference's
``<publisher>/<model>/<file>.gguf`` layout (/root/reference/README.md:279-281).
"""

from __future__ import annotations

from pathlib import Path
from typing import Any

import numpy as np

from ..gguf.constants import GGMLType
from ..gguf.writer import GGUFWriter
from .config import ModelConfig


def _np(x) -> np.ndarray:
    arr = np.asarray(x, dtype=np.float32) if getattr(x, "dtype", None) != np.float32 else np.asarray(x)
    return arr


def _rope_interleave(w: np.ndarray, n_heads: int, head_dim: int) -> np.ndarray:
    """Inverse of models.llama._rope_deinterleave: (first-half, second-half)
    feature order back to GGUF's interleaved pairs."""
    d_in = w.shape[0]
    return (
        w.reshape(d_in, n_heads, 2, head_dim // 2)
        .transpose(0, 1, 3, 2)
        .reshape(d_in, n_heads * head_dim)
    )


def config_metadata(cfg: ModelConfig, name: str) -> dict[str, Any]:
    """The GGUF metadata ``ModelConfig.from_gguf_metadata`` reads back."""
    md: dict[str, Any] = {
        "general.architecture": cfg.arch,
        "general.name": name,
        f"{cfg.arch}.block_count": cfg.n_layers,
        f"{cfg.arch}.embedding_length": cfg.d_model,
        f"{cfg.arch}.attention.head_count": cfg.n_heads,
        f"{cfg.arch}.attention.head_count_kv": cfg.n_kv_heads,
        f"{cfg.arch}.attention.key_length": cfg.head_dim,
        f"{cfg.arch}.attention.value_length": cfg.head_dim,
        f"{cfg.arch}.feed_forward_length": cfg.d_ff,
        f"{cfg.arch}.rope.freq_base": cfg.rope_theta,
        f"{cfg.arch}.attention.layer_norm_rms_epsilon": cfg.rms_eps,
        f"{cfg.arch}.context_length": cfg.max_seq_len,
        f"{cfg.arch}.vocab_size": cfg.vocab_size,
    }
    if cfg.is_moe:
        md[f"{cfg.arch}.expert_count"] = cfg.n_experts
        md[f"{cfg.arch}.expert_used_count"] = cfg.n_experts_used
    if cfg.is_mla:
        a = cfg.arch
        md |= {
            f"{a}.attention.key_length": cfg.head_dim,
            f"{a}.attention.key_length_nope": cfg.qk_nope_head_dim,
            f"{a}.attention.value_length": cfg.v_head_dim,
            f"{a}.attention.kv_lora_rank": cfg.kv_lora_rank,
            f"{a}.rope.dimension_count": cfg.qk_rope_head_dim,
            f"{a}.rope.scaling.type": "yarn" if cfg.rope_factor > 1.0 else "none",
            f"{a}.rope.scaling.factor": cfg.rope_factor,
            f"{a}.rope.scaling.original_context_length": cfg.rope_orig_ctx,
            f"{a}.rope.scaling.yarn_beta_fast": cfg.rope_beta_fast,
            f"{a}.rope.scaling.yarn_beta_slow": cfg.rope_beta_slow,
            f"{a}.rope.scaling.yarn_mscale": cfg.rope_mscale,
            f"{a}.rope.scaling.yarn_mscale_all_dim": cfg.rope_mscale_all_dim,
            f"{a}.expert_feed_forward_length": cfg.moe_d_ff,
            f"{a}.expert_shared_count": cfg.n_shared_experts,
            f"{a}.leading_dense_block_count": cfg.n_dense_layers,
            f"{a}.expert_gating_func": 2 if cfg.router_scoring == "sigmoid" else 1,
            f"{a}.expert_weights_scale": cfg.routed_scaling,
        }
        # what a configuration does not have it does not write, as llama.cpp's
        # deepseek2 leaves q_lora_rank out for a model with one query matrix
        if cfg.q_lora_rank:
            md[f"{a}.attention.q_lora_rank"] = cfg.q_lora_rank
        if cfg.hc_mult > 1:
            md |= {
                f"{a}.hyper_connection.count": cfg.hc_mult,
                f"{a}.hyper_connection.sinkhorn_iterations": cfg.hc_sinkhorn_iters,
                f"{a}.hyper_connection.epsilon": cfg.hc_eps,
                f"{a}.hyper_connection.res_clamp_min": cfg.hc_res_clamp_min,
                f"{a}.hyper_connection.res_clamp_max": cfg.hc_res_clamp_max,
            }
    if cfg.n_ssm_layers:
        # llama.cpp's granitehybrid keys: kv heads a layer (0 = a state-space
        # layer), the Mamba-2 sizes, and no rotary embedding unless finetuned
        a = cfg.arch
        md |= {
            f"{a}.attention.head_count_kv": [
                cfg.n_kv_heads if t == "attention" else 0 for t in cfg.layer_types],
            f"{a}.ssm.conv_kernel": cfg.ssm_conv,
            f"{a}.ssm.inner_size": cfg.ssm_d_inner,
            f"{a}.ssm.state_size": cfg.ssm_d_state,
            f"{a}.ssm.time_step_rank": cfg.ssm_n_heads,
            f"{a}.ssm.group_count": cfg.ssm_n_groups,
            f"{a}.ssm.chunk_size": cfg.ssm_chunk,
            f"{a}.rope.scaling.finetuned": cfg.use_rope,
        }
        if cfg.n_expert_only_layers:
            # one sublayer a layer, as llama.cpp's nemotron_h_moe says it: an
            # FFN width at the layers that are experts alone and 0 elsewhere,
            # the routed and the shared experts' widths, the router's form,
            # the latent the routed experts work in, and (this repo's) the
            # share of a layer's experts this chip holds
            md |= {
                f"{a}.feed_forward_length": [
                    cfg.moe_d_ff if t == "experts" else 0 for t in cfg.layer_types],
                f"{a}.expert_feed_forward_length": cfg.moe_d_ff,
                f"{a}.expert_shared_feed_forward_length": cfg.n_shared_experts * cfg.moe_d_ff,
                f"{a}.moe_latent_size": cfg.moe_latent,
                f"{a}.expert_gating_func": 2 if cfg.router_scoring == "sigmoid" else 1,
                f"{a}.expert_weights_scale": cfg.routed_scaling,
                f"{a}.expert_parallel.count": cfg.moe_ep_size,
                f"{a}.expert_parallel.rank": cfg.moe_ep_rank,
            }
    if cfg.n_win_layers:
        # window layers beside full layers: llama.cpp's keys where it has one
        # (a head count a layer, sliding_window and its pattern, freq_base_swa,
        # the yarn set), ours for the gate and the window layers' rotary dims
        a = cfg.arch
        win = [int(t == "window") for t in cfg.layer_types]
        md |= {
            f"{a}.attention.head_count": [cfg.win_n_heads if w else cfg.n_heads for w in win],
            f"{a}.attention.sliding_window": cfg.window,
            f"{a}.attention.sliding_window_pattern": win,
            f"{a}.attention.output_gate": cfg.attn_gate,
            f"{a}.rope.dimension_count": cfg.rope_dim,
            f"{a}.rope.dimension_count_swa": cfg.win_rope_dim,
            f"{a}.rope.freq_base_swa": cfg.win_rope_theta,
            f"{a}.rope.scaling.type": "yarn" if cfg.rope_factor > 1.0 else "none",
            f"{a}.rope.scaling.factor": cfg.rope_factor,
            f"{a}.rope.scaling.original_context_length": cfg.rope_orig_ctx,
            f"{a}.rope.scaling.yarn_beta_fast": cfg.rope_beta_fast,
            f"{a}.rope.scaling.yarn_beta_slow": cfg.rope_beta_slow,
            f"{a}.rope.scaling.attn_factor": cfg.rope_attn_factor,
            f"{a}.expert_feed_forward_length": cfg.moe_d_ff,
            f"{a}.expert_shared_count": cfg.n_shared_experts,
            f"{a}.leading_dense_block_count": cfg.n_dense_layers,
            f"{a}.expert_gating_func": 2 if cfg.router_scoring == "sigmoid" else 1,
            f"{a}.expert_weights_scale": cfg.routed_scaling,
        }
    if cfg.is_sala:
        # Lightning layers beside block-sparse attention (minicpm_sala): kv
        # heads a layer (0 = a layer with a state), the lightning heads, the
        # switches the published config sets (the family runs that
        # combination only), the sparse layers' seven sizes, and where the
        # file's layers lie in the published stack
        a = cfg.arch
        md |= {
            f"{a}.attention.head_count_kv": [
                cfg.n_kv_heads if t == "sparse" else 0 for t in cfg.layer_types],
            f"{a}.linear_attention.key_head_count": cfg.lin_k_heads,
            f"{a}.linear_attention.value_head_count": cfg.lin_v_heads,
            f"{a}.linear_attention.key_length": cfg.lin_k_dim,
            f"{a}.linear_attention.value_length": cfg.lin_v_dim,
            f"{a}.linear_attention.use_rope": True,
            f"{a}.linear_attention.output_gate": True,
            f"{a}.linear_attention.output_norm": True,
            f"{a}.attention.qk_norm": True,
            f"{a}.attention.output_gate": True,
            f"{a}.attention.use_rope": False,
            f"{a}.attention.sparse.kernel_size": cfg.sparse_kernel,
            f"{a}.attention.sparse.kernel_stride": cfg.sparse_stride,
            f"{a}.attention.sparse.block_size": cfg.sparse_block,
            f"{a}.attention.sparse.window_size": cfg.sparse_window,
            f"{a}.attention.sparse.init_blocks": cfg.sparse_init_blocks,
            f"{a}.attention.sparse.topk": cfg.sparse_topk,
            f"{a}.attention.sparse.dense_len": cfg.sparse_dense_len,
            f"{a}.pipeline.first_layer": cfg.stage_first_layer,
            f"{a}.pipeline.depth": cfg.stage_depth,
        }
    elif cfg.n_lin_layers:
        # gated-delta-rule layers beside gated attention (qwen3next): kv heads
        # a layer as granitehybrid writes them (0 = a layer with a state), the
        # linear layers' five sizes, the attention gate and norms, the router's
        # form, the shared expert's gate, and the share of a layer's experts
        # this chip holds. norm_zero_centered says the published gains are w
        # of x (1 + w): a loader folds 1 + w into the leaf
        a = cfg.arch
        md |= {
            f"{a}.attention.head_count_kv": [
                cfg.n_kv_heads if t == "attention" else 0 for t in cfg.layer_types],
            f"{a}.linear_attention.key_head_count": cfg.lin_k_heads,
            f"{a}.linear_attention.value_head_count": cfg.lin_v_heads,
            f"{a}.linear_attention.key_length": cfg.lin_k_dim,
            f"{a}.linear_attention.value_length": cfg.lin_v_dim,
            f"{a}.linear_attention.conv_kernel": cfg.ssm_conv,
            f"{a}.full_attention_interval": cfg.layer_types.index("attention") + 1,
            f"{a}.rope.dimension_count": cfg.rope_dim,
            f"{a}.attention.output_gate": cfg.attn_out_gate,
            f"{a}.attention.qk_norm": cfg.qk_norm,
            f"{a}.attention.norm_zero_centered": True,
            f"{a}.expert_feed_forward_length": cfg.moe_d_ff,
            f"{a}.expert_shared_count": cfg.n_shared_experts,
            f"{a}.expert_shared_gate": cfg.shared_gate,
            f"{a}.expert_gating_func": 2 if cfg.router_scoring == "sigmoid" else 1,
            f"{a}.expert_weights_scale": cfg.routed_scaling,
            f"{a}.expert_parallel.count": cfg.moe_ep_size,
            f"{a}.expert_parallel.rank": cfg.moe_ep_rank,
        }
    if cfg.arch in ("granite", "granitehybrid", "minicpm_sala"):
        md[f"{cfg.arch}.embedding_scale"] = cfg.embedding_scale
        md[f"{cfg.arch}.residual_scale"] = cfg.residual_scale
        md[f"{cfg.arch}.logit_scale"] = 1.0 / cfg.logit_scale  # stored as divisor
        if cfg.attention_scale is not None:
            md[f"{cfg.arch}.attention.scale"] = cfg.attention_scale
    return md


def export_params_to_gguf(
    path: str | Path,
    params: dict[str, Any],
    cfg: ModelConfig,
    tokenizer_md: dict[str, Any] | None = None,
    name: str = "exported-model",
    quant: GGMLType = GGMLType.F32,
    norm_quant: GGMLType = GGMLType.F32,
) -> Path:
    if cfg.family != "llama":
        raise NotImplementedError(
            f"{cfg.arch}: no GGUF tensor-name map for the {cfg.family} family; "
            "config_metadata writes its header (benchmark/lib/model_files.py)")
    w = GGUFWriter(path)
    w.add_dict(config_metadata(cfg, name))
    if tokenizer_md:
        w.add_dict(tokenizer_md)

    def put(gguf_name: str, arr: np.ndarray, q: GGMLType) -> None:
        w.add_tensor(gguf_name, arr, q)

    # embeddings / head / final norm — stored [out, in] like llama.cpp writes
    put("token_embd.weight", _np(params["embed"]), quant)
    put("output_norm.weight", _np(params["out_norm"]), norm_quant)
    if "lm_head" in params:
        put("output.weight", _np(params["lm_head"]).T, quant)

    blocks = params["blocks"]
    L = cfg.n_layers
    for i in range(L):
        pre = f"blk.{i}"

        def layer(key: str) -> np.ndarray:
            return _np(blocks[key][i])

        put(f"{pre}.attn_norm.weight", layer("attn_norm"), norm_quant)
        put(f"{pre}.ffn_norm.weight", layer("ffn_norm"), norm_quant)
        wq = _rope_interleave(layer("wq"), cfg.n_heads, cfg.head_dim)
        wk = _rope_interleave(layer("wk"), cfg.n_kv_heads, cfg.head_dim)
        put(f"{pre}.attn_q.weight", wq.T, quant)
        put(f"{pre}.attn_k.weight", wk.T, quant)
        put(f"{pre}.attn_v.weight", layer("wv").T, quant)
        put(f"{pre}.attn_output.weight", layer("wo").T, quant)
        if cfg.attn_bias:
            bq = _rope_interleave(layer("bq")[None], cfg.n_heads, cfg.head_dim)[0]
            bk = _rope_interleave(layer("bk")[None], cfg.n_kv_heads, cfg.head_dim)[0]
            put(f"{pre}.attn_q.bias", bq, GGMLType.F32)
            put(f"{pre}.attn_k.bias", bk, GGMLType.F32)
            put(f"{pre}.attn_v.bias", layer("bv"), GGMLType.F32)
        if cfg.is_moe:
            put(f"{pre}.ffn_gate_inp.weight", layer("router").T, GGMLType.F32)
            put(f"{pre}.ffn_gate_exps.weight", layer("w_gate_e").transpose(0, 2, 1), quant)
            put(f"{pre}.ffn_up_exps.weight", layer("w_up_e").transpose(0, 2, 1), quant)
            put(f"{pre}.ffn_down_exps.weight", layer("w_down_e").transpose(0, 2, 1), quant)
        else:
            put(f"{pre}.ffn_gate.weight", layer("w_gate").T, quant)
            put(f"{pre}.ffn_up.weight", layer("w_up").T, quant)
            put(f"{pre}.ffn_down.weight", layer("w_down").T, quant)
    return w.write()
