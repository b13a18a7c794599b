"""Bytes and seconds of a decode step of the latent-attention / routed-expert
family (``references/mla_moe_mhc.py``), from shapes, and what the program's
counters and the device trace give the family's per-layer readers. Peaks are
``lib/roofline.py``'s. Everything returns ``None`` where the program has no
such span, counter or kernel (a parent commit): the reader then returns
``None`` and the metric is left out of the line.

What a step reads, whatever the kernels (bf16, 2 bytes a number, unless the
configuration quantises): every weight outside the routed experts once (the
embedding table is read by rows), each routed expert that a live row picked
once, and the latent and rotary key of every live token in every layer. A
dense dispatch reads every expert: its step reads more than this bound, and
``moe_decode_step_roofline`` shows by how much.
"""

from __future__ import annotations

from benchmark.lib import reduce_trace
from benchmark.lib.roofline import peaks
from benchmark.lib.spans import planes, window_records

MOE_KEYS = ("experts_hit", "expert_rows_max", "expert_rows", "expert_steps")
MLA_KERNEL = "mla_paged_decode_attention"


def window_moe_counters(src) -> dict | None:
    """The expert-layer counters of the bursts read back inside the window,
    summed: each ``batcher.readback`` span of a decode burst carries its
    burst's sums (``BatcherStats.record_moe``)."""
    w0, w1 = src["window"]
    tot = dict.fromkeys(MOE_KEYS, 0)
    for _, _, t1, attrs in window_records(src, "batcher.readback") or []:
        if attrs and "expert_steps" in attrs and w0 <= t1 < w1:
            for k in MOE_KEYS:
                tot[k] += attrs[k]
    return tot if tot["expert_steps"] else None



def cache_token_bytes(hf: dict) -> float:
    """Cached bytes a token a layer: the latent and the shared rotary key."""
    return 2.0 * (hf["kv_lora_rank"] + hf["qk_rope_head_dim"])


def expert_bytes(hf: dict) -> float:
    """One routed expert's three matrices."""
    return 2.0 * 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def non_expert_weight_bytes(hf: dict) -> float:
    """Every weight a decode step reads whole, outside the routed experts:
    attention's five matrices and two norms' worth of small leaves, the two
    mixers, the dense FFN of the leading layers, router and shared expert of
    the others, the final norm and the head."""
    d, hq, L = hf["hidden_size"], hf["num_attention_heads"], hf["num_hidden_layers"]
    dn, dr, dv = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"]
    rq, rkv, n = hf["q_lora_rank"], hf["kv_lora_rank"], hf["hc_mult"]
    attn = d * rq + rq * hq * (dn + dr) + d * (rkv + dr) + rkv * hq * (dn + dv) + hq * dv * d
    mixers = 2 * (n * d * (n * n + 2 * n))
    norms = 2 * d + rq + rkv
    dense_l = hf["first_k_dense_replace"]
    dense = 3 * d * hf["intermediate_size"]
    moe = d * hf["n_routed_experts"] + 3 * d * hf["n_shared_experts"] * hf["moe_intermediate_size"]
    return 2.0 * (L * (attn + mixers + norms) + dense_l * dense + (L - dense_l) * moe
                  + d + d * hf["vocab_size"])


def decode_step_bytes(hf: dict, experts_hit_per_layer: float, live_tokens: float,
                      rows: float) -> float:
    moe_layers = hf["num_hidden_layers"] - hf["first_k_dense_replace"]
    return (non_expert_weight_bytes(hf)
            + moe_layers * experts_hit_per_layer * expert_bytes(hf)
            + hf["num_hidden_layers"] * live_tokens * cache_token_bytes(hf)
            + 2.0 * rows * hf["hidden_size"])


def kernel_call_bytes(hf: dict, live_tokens: float) -> float:
    """What one call of the absorbed kernel (one layer, every slot) must
    read: the live tokens' latents and rotary keys."""
    return live_tokens * cache_token_bytes(hf)


def live_tokens(src) -> float | None:
    """Live pool tokens, averaged over the window's samples."""
    pools = [s["pool"] for s in src["samples"] if s.get("pool")]
    if not pools:
        return None
    return sum(p["blocks_live"] * p["block_tokens"] for p in pools) / len(pools)


def _device_lines(src) -> list[dict]:
    return [l for n, l in (planes(src) or {}).items() if reduce_trace.is_device_plane(n)]


def kernel_durations_ns(src, kernel: str = MLA_KERNEL) -> list[int]:
    """Durations of the custom calls that carry the kernel's fixed name, on
    the first device plane (a one-chip cell has one)."""
    devs = _device_lines(src)
    if not devs:
        return []
    out = []
    for name, _, d in devs[0].get(reduce_trace.OPS_LINE, []):
        label, opcode = reduce_trace.op_label(name)
        if opcode == "custom-call" and kernel in label:
            out.append(d)
    return out


def decode_step_seconds(src) -> float | None:
    """Device seconds of one decode step: the burst decode program's launches
    that lie WHOLLY inside the traced span (the first and the last by start
    time may be cut by its edges and are left out), over the steps of a
    burst."""
    burst = src["engine"].get("decode_burst")
    devs = _device_lines(src)
    if not devs or not burst:
        return None
    ev = sorted((s, d) for name, s, d in devs[0].get(reduce_trace.MODULES_LINE, [])
                if "decode" in reduce_trace.program_name(name)
                and "ext" not in reduce_trace.program_name(name))
    whole = ev[1:-1] if len(ev) >= 3 else ev
    if not whole:
        return None
    return sum(d for _, d in whole) / len(whole) / 1e9 / burst


def bandwidth(src) -> float:
    return peaks(src["device"]["kind"])["hbm_bytes_per_s"] * src["device"]["count"]
