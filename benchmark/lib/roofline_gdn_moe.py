"""Bytes, operations and seconds of the linear-attention / gated-attention
family with a share of the experts (``references/gdn_moe.py``), from shapes
and from what a run itself counted, and what the program's spans, counters
and the device trace give the ``gdn_*`` readers. Peaks are
``lib/roofline.py``'s; the trace helpers are ``lib/roofline_mla_moe.py``'s.
Everything returns ``None`` where the program has no such span, counter or
kernel (a parent commit, another family): the reader then returns ``None``
and the metric is left out of the line.

What a decode step must move, whatever the kernels (bf16 weights and KV, 2
bytes a number; the recurrent state float32): every weight OUTSIDE the routed
experts once with the head (the embedding table is read by rows); each routed
expert held here that a live row picked, once (``experts_hit`` is the run's
own count: a pick of an expert on another chip reads nothing here); for every
slot whose state the step moved (``state_slots_moved``, the program's count:
the live rows) its state of every linear layer once in and once out, and its
convolution tail likewise; the keys and values of every live token in the
full-attention layers.

What a chunk launch must compute at least: the projections of its real tokens
in both kinds of layer, the convolution's taps, the delta rule a token at a
time (a decay, two reads and a write of a [d_k, d_v] state a value head: 7
operations an element; the chunked form's triangular solve and its [C, C]
products are the program's cost, not the model's), causal attention over the
(query, key) pairs its rows really have, the router, the shared expert, the
(token, pick) pairs whose expert is HELD here (the window's own held share of
the decode picks: exact under a silent router, the router's own mean under a
live one), and one head row a prompt row.

The rows, experts and slots a device time is priced against are the TRACED
SPAN's own (``batcher.readback`` spans of decode bursts, ``batcher.admit``
records of chunk launches), not the window's mean (PERF.md, PR 36; ROADMAP B1).
"""

from __future__ import annotations

from benchmark.lib.roofline import peaks
from benchmark.lib.roofline_mla_moe import (  # noqa: F401 — the readers' one import
    bandwidth, decode_step_seconds, kernel_durations_ns, live_tokens)
# a chunk launch's record and its device seconds are the latent families' own
from benchmark.lib.roofline_mla_plain import (  # noqa: F401
    CHUNK_KEYS, chunk_launches, span_chunks)
from benchmark.lib.spans import readback_sums, traced_span

STATE_KERNEL = "gated_delta_step"


def is_family(hf: dict) -> bool:
    return "linear_num_value_heads" in hf


def kinds(hf: dict) -> tuple[int, int]:
    """(linear layers, full-attention layers)."""
    full = hf["num_hidden_layers"] // hf["full_attention_interval"]
    return hf["num_hidden_layers"] - full, full


def chips(hf: dict) -> int:
    return int((hf.get("expert_parallel") or {}).get("chips", 1))


def conv_dim(hf: dict) -> int:
    return (2 * hf["linear_num_key_heads"] * hf["linear_key_head_dim"]
            + hf["linear_num_value_heads"] * hf["linear_value_head_dim"])


def _linear_params(hf: dict) -> int:
    d, hv = hf["hidden_size"], hf["linear_num_value_heads"]
    vd = hv * hf["linear_value_head_dim"]
    return (d * (conv_dim(hf) + vd) + d * 2 * hv + hf["linear_conv_kernel_dim"] * conv_dim(hf)
            + 2 * hv + hf["linear_value_head_dim"] + vd * d + d)


def _attn_params(hf: dict) -> int:
    d, hq, hkv, hd = (hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"],
                      hf["head_dim"])
    return d * 2 * hq * hd + 2 * d * hkv * hd + hq * hd * d + 2 * hd + d


def _expert_params(hf: dict) -> int:
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def _moe_fixed_params(hf: dict) -> int:
    """Router (over ALL the experts), shared expert, its gate and the norm of
    one layer."""
    d = hf["hidden_size"]
    return (d * hf["num_experts"] * chips(hf) + 3 * d * hf["shared_expert_intermediate_size"]
            + 2 * d)


def param_count(hf: dict) -> int:
    """Every parameter of the tree the program serves (head untied;
    ``num_experts`` counts the experts held here)."""
    lin, full = kinds(hf)
    d, L = hf["hidden_size"], hf["num_hidden_layers"]
    return (lin * _linear_params(hf) + full * _attn_params(hf)
            + L * (_moe_fixed_params(hf) + hf["num_experts"] * _expert_params(hf))
            + d + 2 * d * hf["vocab_size"])


def expert_bytes(hf: dict) -> float:
    return 2.0 * _expert_params(hf)


def non_expert_weight_bytes(hf: dict) -> float:
    """Every weight a decode step reads whole, outside the routed experts."""
    lin, full = kinds(hf)
    d = hf["hidden_size"]
    return 2.0 * (lin * _linear_params(hf) + full * _attn_params(hf)
                  + hf["num_hidden_layers"] * _moe_fixed_params(hf) + d + d * hf["vocab_size"])


def state_layer_bytes(hf: dict) -> float:
    """One slot's float32 state of one linear layer."""
    return 4.0 * (hf["linear_num_value_heads"] * hf["linear_key_head_dim"]
                  * hf["linear_value_head_dim"])


def tail_layer_bytes(hf: dict) -> float:
    """One slot's convolution tail of one layer (bf16, the kernel's rows)."""
    return 2.0 * hf["linear_conv_kernel_dim"] * conv_dim(hf)


def kv_token_bytes(hf: dict) -> float:
    """Keys and values of one token in one full-attention layer."""
    return 2.0 * 2 * hf["num_key_value_heads"] * hf["head_dim"]


def decode_step_bytes(hf: dict, slots_moved: float, kv_tokens: float,
                      experts_hit_per_layer: float) -> float:
    lin, full = kinds(hf)
    state = 2.0 * slots_moved * lin * (state_layer_bytes(hf) + tail_layer_bytes(hf))
    return (non_expert_weight_bytes(hf)
            + hf["num_hidden_layers"] * experts_hit_per_layer * expert_bytes(hf)
            + state + full * kv_tokens * kv_token_bytes(hf)
            + 2.0 * slots_moved * hf["hidden_size"])


def state_step_call_bytes(hf: dict, slots_moved: float) -> float:
    """What one call of ``gated_delta_step`` (one layer) must move: the
    listed slots' state once in and once out."""
    return 2.0 * slots_moved * state_layer_bytes(hf)


def chunk_min_flops(hf: dict, rows: float, tokens: float, pairs: float,
                    held_share: float) -> float:
    """The least operations chunk launches of ``rows`` prompt rows, ``tokens``
    real tokens and ``pairs`` causal (query, key) pairs need (module
    docstring), multiply-adds counted as two; ``held_share`` of a token's
    picks land on an expert held here."""
    lin, full = kinds(hf)
    d, hq, hd = hf["hidden_size"], hf["num_attention_heads"], hf["head_dim"]
    hv = hf["linear_num_value_heads"]
    vd = hv * hf["linear_value_head_dim"]
    state = hv * hf["linear_key_head_dim"] * hf["linear_value_head_dim"]
    linear = (2 * (d * (conv_dim(hf) + vd) + d * 2 * hv + vd * d)
              + 2 * hf["linear_conv_kernel_dim"] * conv_dim(hf) + 7 * state)
    attn = 2 * (d * 2 * hq * hd + 2 * d * hf["num_key_value_heads"] * hd + hq * hd * d)
    moe = 2 * (d * hf["num_experts"] * chips(hf) + 3 * d * hf["shared_expert_intermediate_size"]
               + held_share * hf["num_experts_per_tok"] * _expert_params(hf))
    return (tokens * (lin * linear + full * attn + hf["num_hidden_layers"] * moe)
            + pairs * full * hq * 4 * hd + rows * 2 * d * hf["vocab_size"])


def _bursts(src, lo: float, hi: float) -> dict | None:
    """What the decode bursts read back in [lo, hi) counted, summed."""
    c = readback_sums(src, lo, hi)
    return c if c.get("state_steps") and c.get("expert_steps") else None


def window_bursts(src) -> dict | None:
    return _bursts(src, *src["window"])


def span_bursts(src) -> dict | None:
    return _bursts(src, *traced_span(src))


def step_means(c: dict) -> tuple[float, float, float]:
    """(slots whose state a step moved, live rows, experts hit a layer) of a
    mean step of the bursts ``c`` sums."""
    return (c["state_slots_moved"] / c["state_steps"], c["state_rows"] / c["state_steps"],
            c["experts_hit"] / c["expert_steps"])


def held_share(c: dict) -> float | None:
    return c["moe_picks_held"] / c["moe_picks"] if c.get("moe_picks") else None


def chunk_mfu(src) -> float | None:
    """The chunk launches' share of the chip's bf16 peak, in per cent: the
    least operations of a mean launch of the span's own records, times the
    launches the trace holds whole, over their device seconds."""
    chunks, dev, bursts = span_chunks(src), chunk_launches(src), window_bursts(src)
    share = held_share(bursts) if bursts else None
    if not chunks or not dev or share is None:
        return None
    seconds, launches = dev
    tot = {k: sum(a[k] for a in chunks) for k in CHUNK_KEYS}
    need = chunk_min_flops(src["config"], tot["rows"], tot["tokens"], tot["pairs"],
                           share) / len(chunks)
    peak = peaks(src["device"]["kind"])["bf16_flops_per_s"] * src["device"]["count"]
    return 100.0 * need * launches / seconds / peak


def decode_scope_share(src, scope: str = "seq/linear") -> float | None:
    """Per cent of the decode programs' device time under ``scope``."""
    from benchmark.lib import scopes

    t = scopes.table(src)
    if t is None:
        return None
    progs = {p for p, l in t["launches"].items() if l["kind"] == "decode"}
    total = sum(t["launches"][p]["ns"] for p in progs)
    under = sum(ns for (p, s), (ns, _) in t["ops"].items()
                if p in progs and s and (s == scope or s.startswith(scope + "/")))
    return 100.0 * under / total if total and under else None
