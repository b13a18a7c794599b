"""Bytes and seconds of a decode step of the window / full attention family
with routed experts (``references/swa_gated_moe.py``), from shapes, and what
the program's counters and the device trace give the family's per-layer
readers. Peaks are ``lib/roofline.py``'s. Everything returns ``None`` where
the program has no such span, counter or kernel (a parent commit, another
family): the reader then returns ``None`` and the metric is left out of the
line.

What a step reads, whatever the kernels (bf16, 2 bytes a number): every
weight outside the routed experts once with the head (the embedding table is
read by rows), each routed expert that a live row picked once, the keys and
values of every live token in every full layer, and of the last
``sliding_window`` tokens (or fewer) of every live row in every window layer.

The rows, contexts and experts a device time is priced against are the TRACED
SPAN's own (the ``batcher.readback`` spans that end inside it), not the
window's mean: a kernel whose time follows the live rows read 87 % or 79 % by
the span's luck when priced at the window's (PERF.md, PR 36).
"""

from __future__ import annotations

from benchmark.lib.roofline_mla_moe import (  # noqa: F401 — the readers' one import
    bandwidth, decode_step_seconds, kernel_durations_ns, window_moe_counters)
# the same prefill programs under the same names as the state-space family's
from benchmark.lib.roofline_ssm_hybrid import prefill_launches  # noqa: F401
from benchmark.lib.spans import traced_span, window_records

WINDOW_KERNEL = "window_decode_attention"
FULL_KERNEL = "paged_decode_attention"
KEYS = ("win_tokens", "full_tokens", "win_steps", "experts_hit", "expert_rows", "expert_steps")


def is_family(hf: dict) -> bool:
    return "sliding_window" in hf and "num_attention_heads_per_layer" in hf


def kinds(hf: dict) -> tuple[int, int]:
    """(window layers, full layers)."""
    win = sum(t == "sliding_attention" for t in hf["layer_types"])
    return win, hf["num_hidden_layers"] - win


def moe_layers(hf: dict) -> int:
    return sum(m != "dense" for m in hf["mlp_layer_types"])


def kv_token_bytes(hf: dict) -> float:
    """Keys and values of one token in one layer of either kind."""
    return 2.0 * 2 * hf["num_key_value_heads"] * hf["head_dim"]


def expert_bytes(hf: dict) -> float:
    """One routed expert's three matrices."""
    return 2.0 * 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def non_expert_weight_bytes(hf: dict) -> float:
    """Every weight a decode step reads whole, outside the routed experts: a
    layer's attention by ITS head count (wq, wo, the gate, wk, wv) and two
    norms, the dense MLP of the leading layers, router, selection bias and
    shared expert of the others, the final norm and the head."""
    d, hd, hkv = hf["hidden_size"], hf["head_dim"], hf["num_key_value_heads"]
    gate = 1 if hf.get("gating") else 0
    attn = sum(2 * d * h * hd + 2 * d * hkv * hd + gate * d * h + 2 * d
               for h in hf["num_attention_heads_per_layer"])
    n_moe = moe_layers(hf)
    dense = (hf["num_hidden_layers"] - n_moe) * 3 * d * hf["intermediate_size"]
    moe = n_moe * (d * hf["num_experts"] + hf["num_experts"]
                   + 3 * d * hf["shared_expert_intermediate_size"])
    return 2.0 * (attn + dense + moe + d + d * hf["vocab_size"])


def decode_step_bytes(hf: dict, experts_hit_per_layer: float, full_tokens: float,
                      win_tokens: float, rows: float) -> float:
    """``full_tokens`` / ``win_tokens``: the keys the live rows of a step see
    in ONE layer of each kind."""
    win, full = kinds(hf)
    return (non_expert_weight_bytes(hf)
            + moe_layers(hf) * experts_hit_per_layer * expert_bytes(hf)
            + (full * full_tokens + win * win_tokens) * kv_token_bytes(hf)
            + 2.0 * rows * hf["hidden_size"])


def kernel_call_bytes(hf: dict, tokens: float) -> float:
    """What one call of either attention kernel (one layer, every slot) must
    read: the keys and values of the tokens its live rows see."""
    return tokens * kv_token_bytes(hf)


def burst_counters(src, lo: float, hi: float) -> dict | None:
    """The window-layer and expert-layer counters of the decode bursts read
    back in [lo, hi), summed: each ``batcher.readback`` span of a decode burst
    carries its burst's sums (``BatcherStats.record_window`` / ``record_moe``)."""
    tot = dict.fromkeys(KEYS, 0)
    for _, _, t1, attrs in window_records(src, "batcher.readback") or []:
        if attrs and "win_steps" in attrs and lo <= t1 < hi:
            for k in KEYS:
                tot[k] += attrs.get(k, 0)
    return tot if tot["win_steps"] else None


def window_counters(src) -> dict | None:
    return burst_counters(src, *src["window"])


def span_counters(src) -> dict | None:
    return burst_counters(src, *traced_span(src))
