"""Bytes and operations of a decode step of the state-space / attention
hybrid family (``references/ssm_hybrid.py``), from shapes, and what the
program's counters and the device trace give the family's per-layer readers.
Peaks are ``lib/roofline.py``'s; the trace helpers are ``roofline_mla_moe``'s.
Everything returns ``None`` where the program has no such span, counter or
kernel (a parent commit): the reader then returns ``None`` and the metric is
left out of the line.

What a step must move, whatever the kernels (bf16 weights and KV, 2 bytes a
number; the recurrent state float32): every weight once, the head included
(the embedding table is read by rows); for every LIVE row its state of every
state-space layer once in and once out, and its convolution tail likewise;
the keys and values of every live token in the attention layers. A kernel
that also moves the state of slots that hold no request moves more than this
bound, and ``ssm_decode_step_roofline`` shows by how much (since PR 36 the
state kernel moves the slots that hold a request, and no others).

The rows a device time is priced against are the TRACED SPAN's own (the
``batcher.readback`` spans that end inside it: ``span_live_rows``), not the
window's mean: since PR 36 the state kernel's time follows the live rows, and
the window's 24.2 rows over the mean call of a span that held 21.9 read 87 %
where the span's own rows give 79 %; a span at 18 rows under a window at 24
would have read 105 % (PERF.md, PR 36 and PR 39). ``ssm_rows_live_avg``, a
counter and no share of a peak, stays the window's.
"""

from __future__ import annotations

from benchmark.lib.roofline_mla_moe import (  # noqa: F401 — the readers' one import
    bandwidth, decode_step_seconds, kernel_durations_ns, live_tokens)
from benchmark.lib.spans import readback_sums, traced_span

STATE_KERNEL = "ssm_state_step"
# the programs that prefill prompts of this family, as the trace names them
PREFILL_PROGRAMS = ("prefill_chunk_group", "prefill1", "admit_fused_paged",
                    "admit_many_fused_paged")


def kinds(hf: dict) -> tuple[int, int]:
    """(state-space layers, attention layers)."""
    lm = sum(t == "mamba" for t in hf["layer_types"])
    return lm, hf["num_hidden_layers"] - lm


def d_inner(hf: dict) -> int:
    return hf["mamba_n_heads"] * hf["mamba_d_head"]


def conv_dim(hf: dict) -> int:
    return d_inner(hf) + 2 * hf["mamba_n_groups"] * hf["mamba_d_state"]


def weight_bytes(hf: dict) -> float:
    """Every weight a decode step reads whole: both kinds' mixers, the shared
    MLP and two norms a layer, the final norm and the head."""
    d, ff, h = hf["hidden_size"], hf["shared_intermediate_size"], hf["mamba_n_heads"]
    hq, hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd, di, c = d // hq, d_inner(hf), conv_dim(hf)
    lm, la = kinds(hf)
    mlp = 3 * d * ff + 2 * d
    mamba = d * (di + c + h) + hf["mamba_d_conv"] * c + c + 3 * h + di + di * d
    attn = d * hd * (hq + 2 * hkv) + hq * hd * d
    return 2.0 * (lm * (mamba + mlp) + la * (attn + mlp) + d + d * hf["vocab_size"])


def state_layer_bytes(hf: dict) -> float:
    """One slot's float32 state of one state-space layer."""
    return 4.0 * hf["mamba_n_heads"] * hf["mamba_d_head"] * hf["mamba_d_state"]


def tail_layer_bytes(hf: dict) -> float:
    """One slot's convolution tail of one layer (bf16, ``mamba_d_conv`` rows)."""
    return 2.0 * hf["mamba_d_conv"] * conv_dim(hf)


def kv_token_bytes(hf: dict) -> float:
    """Keys and values of one token in one attention layer."""
    return 2.0 * 2 * hf["num_key_value_heads"] * (hf["hidden_size"] // hf["num_attention_heads"])


def decode_step_bytes(hf: dict, live_rows: float, live_kv_tokens: float) -> float:
    lm, la = kinds(hf)
    state = 2.0 * live_rows * lm * (state_layer_bytes(hf) + tail_layer_bytes(hf))
    return (weight_bytes(hf) + state + la * live_kv_tokens * kv_token_bytes(hf)
            + 2.0 * live_rows * hf["hidden_size"])


def state_step_call_bytes(hf: dict, live_rows: float) -> float:
    """What one call of ``ssm_state_step`` (one layer, every slot) must move:
    the live rows' state once in and once out."""
    return 2.0 * live_rows * state_layer_bytes(hf)


def state_step_call_ops(hf: dict, live_rows: float) -> float:
    """Multiply-adds of one call, as operations: decay x state + dt x B (2
    an element) and the read-out C . S (2 an element)."""
    elements = hf["mamba_n_heads"] * hf["mamba_d_head"] * hf["mamba_d_state"]
    return 4.0 * live_rows * elements


def rows_a_step(src, lo: float, hi: float) -> float | None:
    """``state_rows`` (live rows x steps) over ``state_steps`` of the decode
    bursts read back in [lo, hi): each ``batcher.readback`` span of such a
    burst carries its own (``BatcherStats.record_state``)."""
    c = readback_sums(src, lo, hi)
    return c["state_rows"] / c["state_steps"] if c.get("state_steps") else None


def live_rows(src) -> float | None:
    """Live rows a step, the window's mean."""
    return rows_a_step(src, *src["window"])


def span_live_rows(src) -> float | None:
    """Live rows a step of the bursts read back inside the traced span: what
    the span's device times are priced against."""
    return rows_a_step(src, *traced_span(src))


def prefill_launches(src) -> tuple[float, int] | None:
    """(device seconds, launches) of the family's prefill programs that lie
    wholly inside the traced span."""
    programs = src["trace"].get("programs", {})
    found = [programs[p] for p in PREFILL_PROGRAMS if programs.get(p, {}).get("launches")]
    if not found:
        return None
    return sum(p["seconds"] for p in found), sum(p["launches"] for p in found)
