"""Llama-family transformer in pure functional JAX.

One module covers the whole north-star zoo (BASELINE.md) and beyond:
Llama-3 (dense), Granite-3.x (dense + embedding/residual/attention/logit
multipliers), Mixtral (MoE FFN), Qwen2 (QKV biases), and Gemma (GeGLU,
(1+w) RMSNorm, scaled tied embeddings) — in GGUF these differ only by
metadata scales and a handful of family flags (models.config), not by
topology.

TPU-first structure: all per-layer weights carry a leading ``[L]`` axis and
the layer stack runs as a single ``lax.scan`` — one compiled block regardless
of depth, with the full KV cache riding the scan as carry (in-place updates;
see _attention_block for the measured design rationale). No Python loops, no
dynamic shapes under jit.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import PartitionSpec as P

from ..ops.flash_attention import (
    chunk_block_multiple,
    flash_attention_auto,
    flash_attention_chunk_auto,
    flash_attention_chunk_kvq_auto,
)
from ..ops.kvcache import KVQ, kv_update_slice
from ..ops.kvcache import is_quantized as kv_is_quantized
from ..ops.layers import (
    apply_rope,
    gqa_attention_hmajor,
    rms_norm,
    rope_cos_sin,
    swiglu,
)
from ..ops.wquant import flat_rows, mm, q_einsum
from .config import ModelConfig

Params = dict[str, Any]


def family_module(cfg: ModelConfig):
    """The sibling model file that runs ``cfg`` (``cfg.family``: latent
    attention with experts in ``mla_moe``, state-space layers in
    ``ssm_hybrid``), or None for the stack in this file. ``forward``,
    ``forward_decode_paged``, ``make_cache`` and ``init_params`` hand such a
    config to the function of the same name there: same contracts, so the
    batcher, the pool and the sampling never ask which family they serve."""
    if cfg.family == "llama":
        return None
    import importlib

    return importlib.import_module(f"{__package__}.{cfg.family}")


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _qkv_rows(x: jax.Array, p: Params, cfg: ModelConfig) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The layer's q / k / v products as flat rows [B, T, H * D], biases in,
    held apart from the split into heads that follows them (``flat_rows``)."""
    q = mm(x, p["wq"])
    k = mm(x, p["wk"])
    v = mm(x, p["wv"])
    if cfg.attn_bias:  # qwen2-family QKV biases
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    return flat_rows(q, k, v)


def _attention_block(
    x: jax.Array,
    p: Params,
    cfg: ModelConfig,
    k_all: jax.Array,  # FULL cache [B, L, Hkv, S, D] — scan carry, updated in place
    v_all: jax.Array,
    layer: jax.Array,  # int32 scalar — this block's index into the L axis
    start_pos: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    mask: jax.Array,
    attn_window: int | None = None,
    allow_flash: bool = True,
    ring_slot: jax.Array | None = None,  # scalar: shared decode write slot
    mesh=None,  # enables the sp ring-attention prefill when the mesh has sp>1
    fresh_prefill: bool = False,  # static: caller guarantees start_pos == 0
    uniform_start: bool = False,  # static: caller guarantees every row of
    # start_pos is EQUAL (chunked prefill) — enables the cache-backed flash
    # continuation kernel instead of the dense [T, S] f32 score fallback
) -> tuple[jax.Array, jax.Array, jax.Array]:
    b, t, _ = x.shape
    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    s_max = k_all.shape[3]
    q, k, v = _qkv_rows(x, p, cfg)
    q = q.reshape(b, t, hq, d)
    k = k.reshape(b, t, hkv, d)
    v = v.reshape(b, t, hkv, d)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    zero = jnp.zeros((), start_pos.dtype)
    win = attn_window if (attn_window is not None and attn_window < s_max) else s_max
    is_ring_decode = t == 1 and ring_slot is not None

    def _slice_codes(codes):
        if isinstance(layer, int):  # unrolled decode: static slice = view
            return codes[:, layer, :, :win]
        sl = jax.lax.dynamic_slice(codes, (zero, layer, zero, zero, zero),
                                   (b, 1, hkv, win, d))
        if is_ring_decode and mesh is None and jax.default_backend() == "tpu":
            # RING decode only: the attention dot wants the slice S-minor
            # while the cache at rest is write-friendly D/B-minor; left
            # alone, XLA materializes the slice AND relayout-copies it
            # (~300 us/layer at batch 32 — half the decode step).
            # Constraining the slice's layout merges both into one pass:
            # 19.3 -> 15.6 ms/step (granite-2b b32). In the POSITIONAL path
            # the per-row scatter pins a different cache layout and the same
            # constraint backfires into full-cache relayouts (~16x slower,
            # seen in a per-layer ablation of the decode step on the chip).
            sl = with_layout_constraint(
                sl, Layout(major_to_minor=(1, 0, 2, 4, 3))
            )
        return sl[:, 0]

    def layer_slice(cache):
        if not kv_is_quantized(cache):
            return _slice_codes(cache)
        # KVQ: slice codes (with the layout treatment) and scales
        if isinstance(layer, int):
            s_sl = cache.s[:, layer, :, :win]
        else:
            s_sl = jax.lax.dynamic_slice(
                cache.s, (zero, layer, zero, zero), (b, 1, hkv, win)
            )[:, 0]
        return KVQ(q=_slice_codes(cache.q), s=s_sl)

    def as_attn_operand(slab):
        """bf16 slabs cast to q.dtype; quantized slabs pass through (the
        attention fn folds the scales outside the int8 dots)."""
        return slab if kv_is_quantized(slab) else slab.astype(q.dtype)

    if is_ring_decode:
        # Ring decode (the serving hot path): every row writes its fresh
        # k/v at the SAME shared slot, so the cache update is ONE
        # dynamic-update-slice spanning the batch — no per-row scatter
        # (XLA lowers batched ragged scatters to a serialized while-loop,
        # ~4.5 ms/step at batch 8) and no layout conflict (the in-loop DUS
        # pins the cache to its default layout; without it XLA relayouts
        # the whole cache per step for the attention dot, ~3 ms/step).
        # Per-row validity is carried entirely by the ring mask built in
        # forward(); attention reads the full cache at measured ~400 GB/s.
        upd_k = k.transpose(0, 2, 1, 3)[:, None]  # [B,1,Hkv,1,D]
        upd_v = v.transpose(0, 2, 1, 3)[:, None]
        idx = (zero, layer, zero, ring_slot, zero)
        k_all = kv_update_slice(k_all, upd_k, idx)
        v_all = kv_update_slice(v_all, upd_v, idx)

        # attn_window in ring mode is the caller's promise that the ring has
        # not wrapped yet (ring_slot < window and all live tokens sit below
        # it) — then reading cache[:, :, :win] is complete. After the first
        # wrap the caller must pass None and attention reads the full ring.
        out = gqa_attention_hmajor(
            q,
            as_attn_operand(layer_slice(k_all)),
            as_attn_operand(layer_slice(v_all)),
            mask[:, :, :win],
            cfg.attn_scale,
        )
        return mm(out.reshape(b, t, hq * d), p["wo"]), k_all, v_all

    # Positional path (prefill, and decode without a shared ring slot):
    # the caches ride the layer scan as CARRY (not xs/ys — scan ys do not
    # alias xs, which would copy the whole cache every step). The fresh
    # rows scatter into the full array at (b, layer, :, pos, :); the carry
    # buffer's last use in the loop body is this scatter, so XLA performs
    # it in place. Batch is the LEADING cache axis so that, FROM TWO ROWS
    # UP, the vmapped scatter's preferred batch-outermost physical layout
    # IS the default layout — any other order inserts a full-cache relayout
    # copy per layer (measured: 344 ms/step vs 5 ms). The ragged scatter
    # itself lowers to a serialized row loop (~4.5 ms/step at batch 8 — the
    # reason serving uses the ring path), but it also pins the cache layout,
    # which keeps the attention dot reading the cache IN PLACE at ~400 GB/s;
    # every structure that removed the scatter made XLA materialize+relayout
    # the slab per layer and lost more than the scatter costs.
    def write_row(cache_b, rows_b, s):  # cache_b [L,Hkv,S,D]; rows_b [Hkv,T,D]
        return kv_update_slice(cache_b, rows_b[None], (layer, zero, s, zero))

    write = jax.vmap(write_row)
    k_all = write(k_all, k.transpose(0, 2, 1, 3), start_pos)
    v_all = write(v_all, v.transpose(0, 2, 1, 3), start_pos)
    if b == 1 and t > 1 and jax.default_backend() == "tpu":
        # ONE row pins nothing: at batch 1 the compiled text shows the
        # scan's carry as {3,4,2,1,0} (S minor), XLA:TPU's own choice. The
        # chunk kernel's slab (_continue below) is sliced D minor, so the
        # while body then relayouts the WHOLE [1, L, Hkv, S, D] pair in
        # every layer of a continuation chunk, and every chunk once more on
        # entry and exit: two copies of 0.51 ms a layer at 40 x 8 x 2048 x
        # 128 bf16, 41 of a continuation launch's 75 ms (PERF.md section 6,
        # PR 40). Holding the carry, where it leaves the write, to the
        # layout it has at rest (the default, D minor) leaves no copy of the
        # pair anywhere in the program; the fresh-prefill programs compile
        # to the text they had. From two rows up the carry is {4,3,2,1,0}
        # unasked (tests/test_tpu_compile.py reads the compiled text at both
        # widths).
        rest = Layout(major_to_minor=(0, 1, 2, 3, 4))

        def at_rest(cache):  # int8 KV: the codes; the scales [1, L, Hkv, S] stay put
            if kv_is_quantized(cache):
                return KVQ(q=with_layout_constraint(cache.q, rest), s=cache.s)
            return with_layout_constraint(cache, rest)

        k_all, v_all = at_rest(k_all), at_rest(v_all)

    sp_ring = False
    if mesh is not None and t > 1:
        # long prompts only (RING_PREFILL_MIN_TOKENS): t is static under
        # jit, so each prefill bucket's program bakes its own ring-vs-dense
        # decision and short prompts keep the single-chip prefill lane
        from ..parallel.ring_attention import use_ring_prefill

        sp_ring = use_ring_prefill(mesh, t)

    # flash kernels under a mesh are shard_mapped, heads on tp (_on_mesh):
    # q/k/v [B, T, H, D] time-major, cache slabs [B, Hkv, S, D] head-major
    h_ax = _tp_heads_axis(mesh)
    tmajor = P(None, None, h_ax, None)
    hmajor = P(None, h_ax, None, None)
    hmajor_s = P(None, h_ax, None)
    if t > 1 and (sp_ring or (cfg.use_flash_attention and allow_flash)):
        # prefill at start_pos 0: the cache holds exactly k/v, so causal
        # attention over the fresh block equals attention over the cache.
        # At start_pos > 0 (chunked prefill) the fresh block misses earlier
        # cache entries, so fall back to full-cache attention — lax.cond
        # executes only the taken branch per step.
        def _fresh_block(ops):
            q, k, v = ops
            if sp_ring:
                # sequence-parallel prefill: T sharded on sp, K/V blocks
                # rotate the ring via ppermute (parallel/ring_attention) —
                # the long-context path where one chip cannot hold [T, T]
                from ..parallel.ring_attention import ring_attention

                return ring_attention(q, k, v, cfg.attn_scale, mesh)
            return _on_mesh(
                lambda q, k, v: flash_attention_auto(q, k, v, cfg.attn_scale),
                mesh, (tmajor, tmajor, tmajor), tmajor,
            )(q, k, v)

        if fresh_prefill:
            # the caller guarantees start_pos == 0 (single-shot prefill /
            # fused admits). Crucially this SKIPS COMPILING the dense
            # branch: lax.cond compiles both sides, and the dense
            # [B, Hkv, G, T, S] scores buffer at long context is itself a
            # compile-time OOM (16k x 16k f32 = 32 GB)
            out = _fresh_block((q, k, v))
        else:
            def _chunk_tileable(dt, quantized: bool) -> bool:
                # mirror of the chunk kernels' block_k halving: the window
                # must divide by SOME power-of-two tile >= the operand's
                # sublane multiple (int8 codes need 32 rows), or the kernel
                # raises at trace time mid-serving (an odd max_seq like
                # 4600 is accepted by the batcher but only the dense path
                # can serve it)
                mult = chunk_block_multiple(quantized, jnp.dtype(dt).itemsize)
                bk = 512
                while win % bk and bk > mult:
                    bk //= 2
                return win % bk == 0

            def _continue(ops):
                # chunk continuation without the dense [T, win] f32 score
                # matrix (~1 GB/layer at a 4.6k window — most of a chunk's
                # wall time); start is a scalar-prefetch operand so ONE
                # program serves every chunk offset at a given window.
                qq = ops[0]
                k_sl = layer_slice(k_all)
                quantized = kv_is_quantized(k_sl)
                if uniform_start and not sp_ring and _chunk_tileable(qq.dtype, quantized):
                    v_sl = layer_slice(v_all)
                    if quantized:
                        # int8 KV: codes + scales stream straight into the
                        # kernel and dequantize per tile IN VMEM — half the
                        # HBM bytes of a bf16 slab and, decisively, no
                        # full-window dequant transient per layer per chunk
                        # (the r4 O(T^2) long-context prefill tail)
                        return _on_mesh(
                            lambda qq, kq, ks, vq, vs, st: flash_attention_chunk_kvq_auto(
                                qq, kq, ks, vq, vs, cfg.attn_scale, st),
                            mesh,
                            (tmajor, hmajor, hmajor_s, hmajor, hmajor_s, P()),
                            tmajor,
                        )(qq, k_sl.q, k_sl.s, v_sl.q, v_sl.s, start_pos[0])
                    return _on_mesh(
                        lambda qq, ks, vs, st: flash_attention_chunk_auto(
                            qq, ks, vs, cfg.attn_scale, st),
                        mesh, (tmajor, hmajor, hmajor, P()), tmajor,
                    )(qq, k_sl.astype(qq.dtype), v_sl.astype(qq.dtype),
                      start_pos[0])
                return gqa_attention_hmajor(
                    qq, as_attn_operand(k_sl),
                    as_attn_operand(layer_slice(v_all)),
                    mask[:, :, :win], cfg.attn_scale,
                )

            out = jax.lax.cond(jnp.all(start_pos == 0), _fresh_block, _continue, (q, k, v))
    else:
        out = gqa_attention_hmajor(
            q,
            as_attn_operand(layer_slice(k_all)),
            as_attn_operand(layer_slice(v_all)),
            mask[:, :, :win],
            cfg.attn_scale,
        )
    return mm(out.reshape(b, t, hq * d), p["wo"]), k_all, v_all


def _moe_ffn(x: jax.Array, p: Params, cfg: ModelConfig) -> jax.Array:
    """Mixtral top-k routed FFN, dense-dispatch form (every expert computes
    every token; routing weights zero the unused ones). Correct everywhere;
    the expert-parallel ``shard_map`` path in parallel/ replaces this on a
    mesh with an ``expert`` axis. Called inside the layer's ``ffn`` scope."""
    with jax.named_scope("router"):
        router_logits = (x @ p["router"]).astype(jnp.float32)  # [B,T,E]
        top_w, top_idx = jax.lax.top_k(router_logits, cfg.n_experts_used)
        top_w = jax.nn.softmax(top_w, axis=-1)  # normalize over the selected k
        combine = jnp.sum(
            jax.nn.one_hot(top_idx, cfg.n_experts, dtype=jnp.float32) * top_w[..., None], axis=-2
        )  # dense combine weights [B,T,E]
    with jax.named_scope("experts"):
        gate = jax.nn.silu(q_einsum("btd,edf->btef", x, p["w_gate_e"]))
        up = q_einsum("btd,edf->btef", x, p["w_up_e"])
        expert_out = q_einsum("btef,efd->bted", gate * up, p["w_down_e"])
        return jnp.einsum("bted,bte->btd", expert_out, combine.astype(x.dtype))


def _embed(params: Params, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    with jax.named_scope("embed"):
        return params["embed"][tokens].astype(jnp.dtype(cfg.dtype)) * cfg.embedding_scale


def _ffn_block(x: jax.Array, p: Params, cfg: ModelConfig, mesh, overlap: bool = False):
    """The layer's second half: norm, the dense MLP or the experts, residual
    add. ``overlap``: the tp ring form of the dense MLP (forward_decode_paged)."""
    with jax.named_scope("ffn"):
        h = rms_norm(x, p["ffn_norm"], cfg.rms_eps, cfg.norm_plus_one)
        if cfg.is_moe:
            if cfg.use_routed_moe:
                from ..parallel.moe import routed_moe_ffn

                with jax.named_scope("experts"):
                    ffn_out = routed_moe_ffn(h, p, cfg, mesh, cfg.moe_capacity_factor)
            else:
                ffn_out = _moe_ffn(h, p, cfg)
        else:
            with jax.named_scope("mlp"):
                if overlap:
                    from ..parallel.overlap import overlap_ffn

                    ffn_out = overlap_ffn(h, p["w_gate"], p["w_up"], p["w_down"],
                                          cfg.mlp_act, mesh)
                else:
                    ffn_out = swiglu(h, p["w_gate"], p["w_up"], p["w_down"], cfg.mlp_act)
        return x + ffn_out * cfg.residual_scale


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,  # int32 [B, T]
    k_cache: jax.Array,  # [B, L, Hkv, S, D] (heads-major, see make_cache)
    v_cache: jax.Array,
    start_pos: jax.Array,  # int32 [B] — write offset per row (0 for prefill)
    attn_window: int | None = None,  # static: attend to cache[:window] only
    mesh=None,  # static: enables the expert-parallel routed-MoE shard_map
    ring_slot: jax.Array | None = None,  # int32 scalar: shared decode write slot
    logit_positions: jax.Array | None = None,  # int32 [B]: lm_head at these only
    fresh_prefill: bool = False,  # static: start_pos==0 guaranteed; skips
    # compiling the dense fallback branch (whose [B,Hkv,G,T,S] scores are a
    # compile-time OOM at long context)
    uniform_start: bool = False,  # static: every row of start_pos is EQUAL
    # (chunked-prefill callers) — the continuation branch then uses the
    # cache-backed flash kernel instead of the dense [T, win] f32 fallback
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (logits [B, T, vocab] f32, new k_cache, new v_cache);
    with ``logit_positions`` (per-row prompt-end indices) the logits are
    [B, 1, vocab] — prefill callers that only sample the next token skip T×
    the lm_head FLOPs and, decisively for long context, the [B, T, vocab]
    f32 materialization (16k × 128k vocab would be 8.4 GB).

    Handles prefill (T > 1, start_pos = 0) and batched decode (T = 1,
    start_pos = current length per row) with one trace. Right-padded prompts
    are safe: pad keys sit at positions only pad queries can see, and decode
    overwrites them in order. ``attn_window`` (a compile-time bucket >= every
    live sequence length) bounds attention reads to the active cache prefix.

    Decode modes (T = 1):
    * ``ring_slot`` given (the serving hot path): the cache S axis is a RING
      indexed by a global step counter shared across rows, not by per-row
      position. Every row's fresh k/v land at slot ``ring_slot``; a row with
      current length p attends to the p+1 ring slots ending at ``ring_slot``
      (its tokens are contiguous there because the batcher aligns each
      admitted prefix to end at the ring head, and every row writes every
      step). One shared slot = one batched cache write per layer — the shape
      XLA compiles to an in-place update at full HBM speed.
    * ``ring_slot`` None (tests, ragged callers): slots equal per-row
      positions, written by a per-layer batched scatter.
    """
    family = family_module(cfg)
    if family is not None:
        return family.forward(
            params, cfg, tokens, k_cache, v_cache, start_pos, attn_window, mesh,
            ring_slot, logit_positions, fresh_prefill, uniform_start)
    b, t = tokens.shape
    s_max = k_cache.shape[3]
    with jax.named_scope("seq/attn"):  # the rotary tables and the mask, once a pass
        positions = start_pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]  # [B,T]
        cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
        key_pos = jnp.arange(s_max, dtype=jnp.int32)
        if t == 1 and ring_slot is not None:
            # ring validity: slot j holds row b's token iff it is one of the
            # start_pos+1 most recent ring slots (ending at ring_slot, wrapped)
            age = jnp.mod(ring_slot - key_pos, s_max)  # [S]
            mask = age[None, None, :] <= start_pos[:, None, None]  # [B,1,S]
        else:
            mask = key_pos[None, None, :] <= positions[:, :, None]  # [B,T,S]

    x = _embed(params, cfg, tokens)

    def block_body(x, k_all, v_all, p, layer, allow_flash=True):
        with jax.named_scope("seq/attn"):
            attn_out, k_all, v_all = _attention_block(
                rms_norm(x, p["attn_norm"], cfg.rms_eps, cfg.norm_plus_one),
                p, cfg, k_all, v_all, layer,
                start_pos, cos, sin, mask, attn_window, allow_flash,
                ring_slot if t == 1 else None, mesh, fresh_prefill, uniform_start,
            )
            x = x + attn_out * cfg.residual_scale
        return _ffn_block(x, p, cfg, mesh), k_all, v_all

    if cfg.decode_unroll and t == 1:
        # Unrolled decode: static layer indices make every cache access a
        # zero-copy view, at ~n_layers x the compile time.
        for l in range(cfg.n_layers):
            p = jax.tree.map(lambda a: a[l], params["blocks"])
            x, k_cache, v_cache = block_body(
                x, k_cache, v_cache, p, l, allow_flash=False
            )
    else:
        def block(carry, inputs):
            x, k_all, v_all = carry
            p, layer = inputs
            return block_body(x, k_all, v_all, p, layer), None

        layer_idx = jnp.arange(cfg.n_layers, dtype=jnp.int32)
        (x, k_cache, v_cache), _ = jax.lax.scan(
            block, (x, k_cache, v_cache), (params["blocks"], layer_idx)
        )

    logits = lm_head_logits(params, cfg, x, logit_positions, t)
    return logits, k_cache, v_cache


def _tp_heads_axis(mesh):
    """The mesh axis that splits attention heads (tp), or None when there is
    no mesh or tp == 1."""
    if mesh is None:
        return None
    from ..parallel.mesh import AXIS_TP

    return AXIS_TP if mesh.shape.get(AXIS_TP, 1) > 1 else None


def _on_mesh(fn, mesh, in_specs, out_specs):
    """A Pallas kernel call under a mesh. pallas_call is not
    GSPMD-partitionable ("Mosaic kernels cannot be automatically
    partitioned"), so inside a sharded jit every kernel is an explicit
    shard_map: heads split on tp — the layout the projections and cache
    specs already pin — everything else replicated. Callers only route here
    when Hkv % tp == 0, so each shard keeps whole GQA groups."""
    if mesh is None or mesh.size == 1:
        return fn
    from jax import shard_map

    return shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)


def _paged_attn_dispatch(q, k_pool, v_pool, tbl, pos, layer, scale: float, mesh):
    """The Pallas paged-decode kernel, shard_mapped over tp when a mesh is
    present (``_on_mesh``): q heads and pool heads shard on tp,
    tables/positions replicate — the same layout pool_spec pins for the XLA
    path. The batcher only routes here when Hkv % tp == 0 (the
    replicated-KV GQA fallback stays on the XLA path)."""
    from ..ops.paged_attention import paged_decode_attention_auto

    h = _tp_heads_axis(mesh)
    qspec = P(None, None, h, None)
    cspec = P(None, None, h, None, None)  # pool codes: heads at index 2
    sspec = P(None, None, h, None)
    rep2, rep1, rep0 = P(None, None), P(None), P()
    layer = jnp.asarray(layer, jnp.int32)
    if kv_is_quantized(k_pool):
        def f(qh, kq, ks, vq, vs, tb, ps, ly):
            return paged_decode_attention_auto(
                qh, KVQ(q=kq, s=ks), KVQ(q=vq, s=vs), tb, ps, ly, scale
            )

        return _on_mesh(
            f, mesh, (qspec, cspec, sspec, cspec, sspec, rep2, rep1, rep0), qspec,
        )(q, k_pool.q, k_pool.s, v_pool.q, v_pool.s, tbl, pos, layer)

    def g(qh, kp, vp, tb, ps, ly):
        return paged_decode_attention_auto(qh, kp, vp, tb, ps, ly, scale)

    return _on_mesh(
        g, mesh, (qspec, cspec, cspec, rep2, rep1, rep0), qspec,
    )(q, k_pool, v_pool, tbl, pos, layer)


def forward_decode_paged(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,  # int32 [B, W] — W == 1 decode, W == k+1 spec verify
    k_pool,             # [NBp, L, Hkv, T, D] paged block pool (or KVQ pair)
    v_pool,
    tbl: jax.Array,     # [B, NB] int32 block table (NB static = max width)
    start_pos: jax.Array,  # int32 [B] — tokens already in each slot's cache
    mesh=None,
    moe_stats: bool = False,  # static: also return the expert-layer counters
    # (models/mla_moe.py; only families with cfg.n_moe_layers have them)
) -> tuple[jax.Array, Any, Any]:
    """Decode forward that reads/writes the paged pool DIRECTLY — no
    ``kv_pool_gather_view`` materialization, no windowed attention, no
    pow2-ladder recompiles (the attention grid spans the whole table width,
    ops/paged_attention.py). Per layer: project q/k/v, rope at the slot's
    positions, scatter the W fresh rows into the pool (quantize-on-write
    under KVQ — identical codes to the view path's ``kv_update_slice``),
    then run the Pallas kernel over the slot's entire paged history
    (write-then-attend: the causal frontier includes the fresh rows).

    Returns (logits [B, W, vocab] f32, k_pool, v_pool). Math mirrors
    ``forward``'s positional path op-for-op outside the attention
    accumulation order (online softmax vs dense), so greedy decode is
    token-identical through the batcher."""
    from ..ops.kvcache import kv_pool_write_rows

    family = family_module(cfg)
    if family is not None:
        out = family.forward_decode_paged(
            params, cfg, tokens, k_pool, v_pool, tbl, start_pos, mesh)
        return out if moe_stats else out[:3]
    b, w = tokens.shape
    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope("seq/attn"):
        positions = start_pos[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
        cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
    x = _embed(params, cfg, tokens)

    # TP_OVERLAP: the row-sharded projections' all-reduce runs as a
    # ppermute ring (parallel/overlap.py) instead of one blocking psum —
    # decode-only (this stack), default off, dense-FFN only (MoE keeps its
    # own dispatch collectives)
    overlap = False
    if mesh is not None:
        from ..parallel.mesh import AXIS_TP
        from ..parallel.overlap import tp_overlap_enabled

        overlap = (tp_overlap_enabled() and not cfg.is_moe
                   and mesh.shape.get(AXIS_TP, 1) > 1
                   and cfg.n_kv_heads % mesh.shape.get(AXIS_TP, 1) == 0)

    def block_body(x, kp, vp, p, layer):
        with jax.named_scope("seq/attn"):
            h = rms_norm(x, p["attn_norm"], cfg.rms_eps, cfg.norm_plus_one)
            q, k, v = _qkv_rows(h, p, cfg)
            q = apply_rope(q.reshape(b, w, hq, d), cos, sin)
            k = apply_rope(k.reshape(b, w, hkv, d), cos, sin)
            v = v.reshape(b, w, hkv, d)
            kp = kv_pool_write_rows(kp, k, tbl, start_pos, layer)
            vp = kv_pool_write_rows(vp, v, tbl, start_pos, layer)
            out = _paged_attn_dispatch(q, kp, vp, tbl, start_pos, layer,
                                       cfg.attn_scale, mesh)
            attn_in = out.reshape(b, w, hq * d)
            if overlap:
                from ..parallel.overlap import overlap_row_proj

                proj = overlap_row_proj(attn_in, p["wo"], mesh)
            else:
                proj = mm(attn_in, p["wo"])
            x = x + proj * cfg.residual_scale
        return _ffn_block(x, p, cfg, mesh, overlap), kp, vp

    def block(carry, inputs):
        x, kp, vp = carry
        p, layer = inputs
        return block_body(x, kp, vp, p, layer), None

    layer_idx = jnp.arange(cfg.n_layers, dtype=jnp.int32)
    (x, k_pool, v_pool), _ = jax.lax.scan(
        block, (x, k_pool, v_pool), (params["blocks"], layer_idx)
    )
    logits = lm_head_logits(params, cfg, x, None, w)
    return logits, k_pool, v_pool


def lm_head_logits(params: Params, cfg: ModelConfig, x: jax.Array,
                   logit_positions: jax.Array | None, t: int) -> jax.Array:
    """Shared output head (norm + lm_head, tied-embedding fallback,
    logit_positions gather): the dense forward and the pipeline-parallel
    forward (parallel/pipeline.py) must never diverge here."""
    with jax.named_scope("head/logits"):
        if logit_positions is not None and t > 1:
            x = jnp.take_along_axis(x, logit_positions[:, None, None], axis=1)  # [B,1,d]
        x = rms_norm(x, params["out_norm"], cfg.rms_eps, cfg.norm_plus_one)
        lm_head = params.get("lm_head")
        if lm_head is None:
            lm_head = params["embed"].T
        return mm(x, lm_head).astype(jnp.float32) * cfg.logit_scale


def ensure_lm_head(params: Params) -> Params:
    """Materialize a contiguous [d_model, vocab] lm_head for tied-embedding
    models. forward() falls back to ``embed.T`` when absent, which is correct
    but leaves the output projection reading a transposed view every decode
    step; serving paths call this once at load so the hot loop gets the
    matmul-native layout (and the quantizer can see the leaf)."""
    if "lm_head" in params:
        return params
    params = dict(params)
    params["lm_head"] = jnp.swapaxes(params["embed"], 0, 1)  # eager: materializes
    return params


def make_cache(
    cfg: ModelConfig, batch: int, seq_len: int | None = None, dtype: str | None = None
) -> tuple[jax.Array, jax.Array]:
    """Zeroed KV cache pair, layout [B, L, Hkv, S, D] — batch-major so the
    per-row scatter's preferred physical layout IS the default layout (see
    _attention_block), heads-major within a row so each (batch, head) slab
    is contiguous and the decode attention dot streams it sequentially; the
    TP axis annotates Hkv and a sequence/ring axis annotates S without
    relayout (SURVEY.md §5). In ring-decode serving the S axis is a ring
    indexed by a shared step counter, not per-row position (see forward).

    With ``cfg.kv_quant == "int8"`` each cache is a ``KVQ`` pytree (int8
    codes + f32 per-position-per-head scales, ops/kvcache.py) in the same
    layout — half the HBM traffic and capacity per step."""
    family = family_module(cfg)
    if family is not None:
        return family.make_cache(cfg, batch, seq_len, dtype)
    s = seq_len or cfg.max_seq_len
    shape = (batch, cfg.n_layers, cfg.n_kv_heads, s, cfg.head_dim)
    if cfg.kv_quant == "int8":
        from ..ops.kvcache import kv_zeros

        return kv_zeros(shape), kv_zeros(shape)
    dt = jnp.dtype(dtype or cfg.dtype)
    return jnp.zeros(shape, dt), jnp.zeros(shape, dt)


# ---------------------------------------------------------------------------
# parameter construction
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Random small-scale init (tests / golden-logit fixtures)."""
    family = family_module(cfg)
    if family is not None:
        return family.init_params(cfg, key)
    dt = jnp.dtype(cfg.dtype)
    keys = iter(jax.random.split(key, 24))

    def rand(*shape):
        return (jax.random.normal(next(keys), shape, jnp.float32) * 0.02).astype(dt)

    L, d, hq, hkv, hd, ff = (
        cfg.n_layers,
        cfg.d_model,
        cfg.n_heads,
        cfg.n_kv_heads,
        cfg.head_dim,
        cfg.d_ff,
    )
    blocks: Params = {
        "attn_norm": jnp.ones((L, d), dt),
        "ffn_norm": jnp.ones((L, d), dt),
        "wq": rand(L, d, hq * hd),
        "wk": rand(L, d, hkv * hd),
        "wv": rand(L, d, hkv * hd),
        "wo": rand(L, hq * hd, d),
    }
    if cfg.attn_bias:
        blocks |= {
            "bq": rand(L, hq * hd),
            "bk": rand(L, hkv * hd),
            "bv": rand(L, hkv * hd),
        }
    if cfg.is_moe:
        e = cfg.n_experts
        blocks |= {
            "router": rand(L, d, e),
            "w_gate_e": rand(L, e, d, ff),
            "w_up_e": rand(L, e, d, ff),
            "w_down_e": rand(L, e, ff, d),
        }
    else:
        blocks |= {"w_gate": rand(L, d, ff), "w_up": rand(L, d, ff), "w_down": rand(L, ff, d)}
    params: Params = {
        "embed": rand(cfg.vocab_size, d),
        "out_norm": jnp.ones((d,), dt),
        "blocks": blocks,
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = rand(d, cfg.vocab_size)
    return params


# -- GGUF layout (the loader is parallel/loader.py) -------------------------


def _rope_deinterleave(w: np.ndarray, n_heads: int, head_dim: int) -> np.ndarray:
    """GGUF llama-family q/k weights expect interleaved-pair rotation
    (ggml "NORM" RoPE); our kernel rotates (first-half, second-half). Permute
    the output features so both agree: out index h*D + 2i+j -> h*D + j*D/2+i.
    """
    d_in = w.shape[0]
    return (
        w.reshape(d_in, n_heads, head_dim // 2, 2)
        .transpose(0, 1, 3, 2)
        .reshape(d_in, n_heads * head_dim)
    )
