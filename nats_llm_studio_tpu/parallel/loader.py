"""Streaming sharded weight loading.

SURVEY.md §7 hard part #2: a 70B GGUF is ~40 GB on disk and ~140 GB as bf16 —
materializing the full pytree before sharding or quantizing it cannot work
there, and on one 16 GB chip it already fails for an 8B file. This is the
repo's one GGUF loader, for every placement. It walks the tensor
index one entry at a time: mmap read -> dequant (native C++ path) -> cast or
quantize on the host -> ``jax.device_put`` with the tensor's NamedSharding ->
host buffer released, so peak host memory is one tensor, not one model.
Stacked [L]-leading leaves are allocated once at their final shape and
sharding, and each layer slice is written into them in place (a donated
dynamic-update-slice), so peak device memory is the final tree plus one
layer slice — never a second copy of a leaf. A one-device mesh is how the
registry loads for unsharded serving.
"""

from __future__ import annotations

import functools
import gc
import logging
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.config import ModelConfig
from ..models.llama import _rope_deinterleave
from ..ops.wquant import (
    QTensor,
    QTensor4,
    quantizable,
    quantize_weight,
    quantize_weight4,
)
from .sharding import param_sharding_rules, scale_spec

log = logging.getLogger(__name__)


@functools.lru_cache(maxsize=None)
def _layer_writer(sharding: NamedSharding):
    """Jitted ``buf[i] = x`` that donates ``buf`` and keeps its sharding, so
    filling a stacked leaf never holds two copies of it."""
    return jax.jit(
        lambda buf, x, i: jax.lax.dynamic_update_index_in_dim(buf, x, i, 0),
        donate_argnums=0, out_shardings=sharding,
    )


def load_params_sharded(
    reader, cfg: ModelConfig, mesh: Mesh, dtype: str | None = None,
    quant: str = "none", group: int = 32,
) -> dict[str, Any]:
    """Build the stacked-params pytree directly on the mesh, one tensor at a
    time.

    Tensor names follow the public GGUF convention (token_embd, blk.N.*,
    output_norm, output). Weights are stored [out, in] (after the reader's
    dim reversal) and transposed here to [in, out] so forward() uses plain
    ``x @ w`` — the layout XLA maps straight onto the MXU.

    ``quant="int8"`` re-quantizes each matmul weight to symmetric
    per-output-channel int8 on the host *before* placement, so device HBM
    holds int8 + scales — the path that fits Llama-3-70B on a v5e-8
    (BASELINE.md config 3), Llama-3-8B on one 16 GB chip, and halves decode
    weight traffic. ``quant="int4"`` goes further: asymmetric grouped
    QTensor4 (``group`` rows per scale/zero-point), ~4.3 bits/weight,
    halving traffic again.
    """
    dt = jnp.dtype(dtype or cfg.dtype)
    if quant not in ("none", "int8", "int4"):
        raise ValueError(f"unknown quant mode {quant!r}")
    if cfg.is_mla:
        raise NotImplementedError(
            f"{cfg.arch}: no GGUF tensor-name map for latent-attention models "
            "yet; the tree to build is models.mla_moe.init_params' (two stacks, "
            "blocks.dense and blocks.moe; "
            + ("w_dq / q_norm / w_uq" if cfg.q_lora_rank else "one wq")
            + (f", mixers of {cfg.hc_mult} streams" if cfg.hc_mult > 1 else ", no mixers")
            + "; the checkpoint's interleaved rotary pairs permuted to (first half, "
            "second half)), placed by param_sharding_rules")
    if cfg.n_ssm_layers:
        raise NotImplementedError(
            f"{cfg.arch}: no GGUF tensor-name map for state-space models yet; "
            "the tree to build is models.ssm_hybrid.init_params' (two stacks, "
            "blocks.mamba and blocks.attn"
            + ("; a third, blocks.moe, for the layers of experts alone: the experts e "
               "with e mod expert_parallel.count == rank, expert e at place e // count; "
               "W_in's columns as w_in [z | xBC] and w_dt" if cfg.n_moe_layers else "")
            + "), placed by param_sharding_rules")
    if cfg.n_win_layers:
        raise NotImplementedError(
            f"{cfg.arch}: no GGUF tensor-name map for window-attention models "
            "yet; the tree to build is models.swa_moe.init_params' (attention "
            "leaves in blocks.full and blocks.win, MLP leaves in blocks.dense "
            "and blocks.moe), placed by param_sharding_rules")
    if cfg.is_sala:
        raise NotImplementedError(
            f"{cfg.arch}: no GGUF tensor-name map for lightning / block-sparse "
            "models yet; the tree to build is models.sala.init_params' (mixers and "
            "each layer's SwiGLU in blocks.linear and blocks.attn by kind; a sparse "
            "layer's in-projection laid out plainly as [q | gate]; the rotary pairs "
            "permuted to (first half, second half); blocks.linear.decay the LOGITS of "
            "the checkpoint's rates a (layer, head)), placed by param_sharding_rules")
    if cfg.n_lin_layers:
        raise NotImplementedError(
            f"{cfg.arch}: no GGUF tensor-name map for linear-attention models "
            "yet; the tree to build is models.gdn_moe.init_params' (mixers in "
            "blocks.linear and blocks.attn, every layer's experts in blocks.moe: "
            "the experts e with e mod expert_parallel.count == rank, expert e at "
            "place e // count; the in-projections' interleaved heads laid out "
            "plainly as [q | k | v | z], [b | a] and [q | gate]; 1 + w folded "
            "into every zero-centred norm gain), placed by param_sharding_rules")
    rules = param_sharding_rules(mesh, cfg)

    def t(name: str) -> np.ndarray:
        return reader.tensor(name).to_numpy()

    def mat(name: str) -> np.ndarray:
        return np.ascontiguousarray(t(name).T)

    def host_leaf(key: str, arr: np.ndarray, spec: P):
        """Host tensor -> (component arrays, their specs, rebuild): one bf16
        array, or the codes/scales of an int8 QTensor / int4 QTensor4.
        ``spec`` is the spec of ``arr`` itself (no [L] axis)."""
        if quant == "int8" and quantizable(key):
            qt = quantize_weight(arr)
            return (qt.q, qt.s), (spec, scale_spec(spec)), QTensor
        if quant == "int4" and quantizable(key):
            # codes AND grouped scales/zeros all keep the weight's spec
            # (see shard_params: the grouped axis shards with the
            # contraction axis, it is not extent-1 like the int8 scale)
            qt = quantize_weight4(arr, group=group)
            return (
                (qt.q, qt.s, qt.z), (spec, spec, spec),
                functools.partial(QTensor4, group=qt.group),
            )
        # cast on the host: the device only ever sees the serving dtype
        return (np.asarray(arr).astype(dt),), (spec,), lambda a: a

    def place(key: str, arr: np.ndarray) -> Any:
        parts, specs, rebuild = host_leaf(key, arr, rules[key])
        return rebuild(*(
            jax.device_put(a, NamedSharding(mesh, sp))
            for a, sp in zip(parts, specs)
        ))

    params: dict[str, Any] = {
        "embed": place("embed", t("token_embd.weight")),
        "out_norm": place("out_norm", t("output_norm.weight")),
    }
    # tied embeddings: materialize the [d, vocab] head now (contiguous,
    # shardable, quantizable) instead of transposing embed every step
    head = "output.weight" if "output.weight" in reader.tensors else "token_embd.weight"
    params["lm_head"] = place("lm_head", mat(head))

    # stacked per-layer leaves: key -> (device buffers, rebuild). The
    # buffers exist at their final [L, ...] shape from layer 0 on; every
    # layer slice is placed with the slice sharding and written in place,
    # and its host and device copies die right after
    stacked: dict[str, tuple[list, Any]] = {}

    def push(i: int, key: str, arr: np.ndarray) -> None:
        full = rules[f"blocks.{key}"]
        parts, specs, rebuild = host_leaf(key, arr, P(*full[1:]))
        if key not in stacked:
            stacked[key] = ([
                jnp.zeros((cfg.n_layers, *a.shape), a.dtype,
                          device=NamedSharding(mesh, P(full[0], *sp)))
                for a, sp in zip(parts, specs)
            ], rebuild)
        bufs = stacked[key][0]
        for j, (a, sp) in enumerate(zip(parts, specs)):
            x = jax.device_put(a, NamedSharding(mesh, sp))
            bufs[j] = _layer_writer(bufs[j].sharding)(bufs[j], x, np.int32(i))

    for i in range(cfg.n_layers):
        pre = f"blk.{i}"
        push(i, "attn_norm", t(f"{pre}.attn_norm.weight"))
        push(i, "ffn_norm", t(f"{pre}.ffn_norm.weight"))
        push(i, "wq", _rope_deinterleave(mat(f"{pre}.attn_q.weight"), cfg.n_heads, cfg.head_dim))
        push(i, "wk", _rope_deinterleave(mat(f"{pre}.attn_k.weight"), cfg.n_kv_heads, cfg.head_dim))
        push(i, "wv", mat(f"{pre}.attn_v.weight"))
        push(i, "wo", mat(f"{pre}.attn_output.weight"))
        if cfg.attn_bias:
            push(i, "bq", _rope_deinterleave(
                t(f"{pre}.attn_q.bias")[None], cfg.n_heads, cfg.head_dim)[0])
            push(i, "bk", _rope_deinterleave(
                t(f"{pre}.attn_k.bias")[None], cfg.n_kv_heads, cfg.head_dim)[0])
            push(i, "bv", t(f"{pre}.attn_v.bias"))
        if cfg.is_moe:
            push(i, "router", mat(f"{pre}.ffn_gate_inp.weight"))
            push(i, "w_gate_e", t(f"{pre}.ffn_gate_exps.weight").transpose(0, 2, 1))
            push(i, "w_up_e", t(f"{pre}.ffn_up_exps.weight").transpose(0, 2, 1))
            push(i, "w_down_e", t(f"{pre}.ffn_down_exps.weight").transpose(0, 2, 1))
        else:
            push(i, "w_gate", mat(f"{pre}.ffn_gate.weight"))
            push(i, "w_up", mat(f"{pre}.ffn_up.weight"))
            push(i, "w_down", mat(f"{pre}.ffn_down.weight"))
        if i % 8 == 7:
            gc.collect()  # drop dequant temporaries promptly on big models

    params["blocks"] = {k: rebuild(*bufs) for k, (bufs, rebuild) in stacked.items()}
    return params
