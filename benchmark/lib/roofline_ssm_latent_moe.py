"""Bytes, operations and seconds of the state-space family whose every layer
is one sublayer (Mamba-2 of several groups, NoPE attention, two-matrix relu^2
experts in a latent of which the chip holds a share:
``references/ssm_latent_moe.py``), from shapes and from what a run itself
counted, and what the program's spans, counters and the device trace give the
``lmoe_*`` readers. Peaks are ``lib/roofline.py``'s; the trace helpers are
``lib/roofline_mla_moe.py``'s and ``lib/roofline_mla_plain.py``'s. Everything
returns ``None`` where the program has no such span, counter or kernel (a
parent commit, another family): the reader then returns ``None`` and the
metric is left out of the line.

What a decode step must move, whatever implements it (bf16 weights and KV, 2
bytes a number; the recurrent state float32): every weight OUTSIDE the routed
experts once with the head (the embedding table is read by rows); each routed
expert held here that a live row picked, once (``experts_hit`` is the run's
own count: a pick of an expert on another chip reads nothing here); for every
slot whose state the step moved (``state_slots_moved``, the program's count)
its state of every Mamba-2 layer once in and once out, and its convolution
tail likewise; the keys and values of every live token in the attention
layers.

What a chunk launch must compute at least: the projections of its real tokens
in every kind of layer, the convolution's taps, the recurrence a token at a
time (a decay, a write and a read of a [P, N] state a head: 6 operations an
element; the chunked form's [Q, Q] products are the program's cost, not the
model's), causal attention over the (query, key) pairs its rows really have,
the router, the latent pair, the shared expert, the (token, pick) pairs whose
expert is HELD here (the window's own held share of the decode picks: the
router's own mean), and one head row a prompt row.

The rows, experts and slots a device time is priced against are the TRACED
SPAN's own (``batcher.readback`` spans of decode bursts, ``batcher.admit``
records of chunk launches), not the window's mean (PERF.md, PR 36; ROADMAP B1).
"""

from __future__ import annotations

from benchmark.lib import reduce_trace
from benchmark.lib.roofline import peaks
from benchmark.lib.roofline_mla_moe import (  # noqa: F401 — the readers' one import
    _device_lines, bandwidth, decode_step_seconds, kernel_durations_ns, live_tokens)
# what the bursts' readback spans counted is the linear-attention family's:
# the same counters (state rows and slots, experts hit, picks held)
from benchmark.lib.roofline_gdn_moe import (  # noqa: F401
    held_share, span_bursts, step_means, window_bursts)
from benchmark.lib.roofline_mla_plain import (  # noqa: F401
    CHUNK_KEYS, chunk_launches, span_chunks)

STATE_KERNEL = "ssm_state_step"
EXPERT_KERNELS = ("moe_hit_experts", "moe_grouped_experts")


def is_family(hf: dict) -> bool:
    return "hybrid_override_pattern" in hf and "moe_latent_size" in hf


def kinds(hf: dict) -> tuple[int, int, int]:
    """(Mamba-2 layers, expert layers, attention layers)."""
    p = hf["hybrid_override_pattern"]
    return p.count("M"), p.count("E"), p.count("*")


def chips(hf: dict) -> int:
    return int((hf.get("expert_parallel") or {}).get("chips", 1))


def d_inner(hf: dict) -> int:
    return hf["mamba_num_heads"] * hf["mamba_head_dim"]


def conv_dim(hf: dict) -> int:
    return d_inner(hf) + 2 * hf["n_groups"] * hf["ssm_state_size"]


def _mamba_params(hf: dict) -> int:
    d, h = hf["hidden_size"], hf["mamba_num_heads"]
    return (d * (d_inner(hf) + conv_dim(hf)) + d * h + (hf["conv_kernel"] + 1) * conv_dim(hf)
            + 3 * h + d_inner(hf) + d_inner(hf) * d + d)


def _attn_params(hf: dict) -> int:
    d, hq, hkv, hd = (hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"],
                      hf["head_dim"])
    return 2 * d * hq * hd + 2 * d * hkv * hd + d


def _expert_params(hf: dict) -> int:
    return 2 * hf["moe_latent_size"] * hf["moe_intermediate_size"]


def _moe_fixed_params(hf: dict) -> int:
    """Router and selection bias (over ALL the experts), the latent pair, the
    shared expert and the norm of one expert layer."""
    d, e = hf["hidden_size"], hf["n_routed_experts"] * chips(hf)
    return (d * e + e + 2 * d * hf["moe_latent_size"]
            + 2 * d * hf["n_shared_experts"] * hf["moe_shared_expert_intermediate_size"] + d)


def param_count(hf: dict) -> int:
    """Every parameter of the tree the program serves (head untied;
    ``n_routed_experts`` counts the experts held here)."""
    m, e, a = kinds(hf)
    d = hf["hidden_size"]
    return (m * _mamba_params(hf) + a * _attn_params(hf)
            + e * (_moe_fixed_params(hf) + hf["n_routed_experts"] * _expert_params(hf))
            + d + 2 * d * hf["vocab_size"])


def expert_bytes(hf: dict) -> float:
    return 2.0 * _expert_params(hf)


def non_expert_weight_bytes(hf: dict) -> float:
    """Every weight a decode step reads whole, outside the routed experts."""
    m, e, a = kinds(hf)
    d = hf["hidden_size"]
    return 2.0 * (m * _mamba_params(hf) + a * _attn_params(hf) + e * _moe_fixed_params(hf)
                  + d + d * hf["vocab_size"])


def state_layer_bytes(hf: dict) -> float:
    """One slot's float32 state of one Mamba-2 layer."""
    return 4.0 * d_inner(hf) * hf["ssm_state_size"]


def tail_layer_bytes(hf: dict) -> float:
    """One slot's convolution tail of one layer (bf16)."""
    return 2.0 * hf["conv_kernel"] * conv_dim(hf)


def kv_token_bytes(hf: dict) -> float:
    """Keys and values of one token in one attention layer."""
    return 2.0 * 2 * hf["num_key_value_heads"] * hf["head_dim"]


def decode_step_bytes(hf: dict, slots_moved: float, kv_tokens: float,
                      experts_hit_per_layer: float) -> float:
    m, e, a = kinds(hf)
    state = 2.0 * slots_moved * m * (state_layer_bytes(hf) + tail_layer_bytes(hf))
    return (non_expert_weight_bytes(hf) + e * experts_hit_per_layer * expert_bytes(hf)
            + state + a * kv_tokens * kv_token_bytes(hf) + 2.0 * slots_moved * hf["hidden_size"])


def state_step_call_bytes(hf: dict, slots_moved: float) -> float:
    """What one call of ``ssm_state_step`` (one layer) must move: the listed
    slots' state once in and once out."""
    return 2.0 * slots_moved * state_layer_bytes(hf)


def chunk_min_flops(hf: dict, rows: float, tokens: float, pairs: float,
                    held_share: float) -> float:
    """The least operations chunk launches of ``rows`` prompt rows, ``tokens``
    real tokens and ``pairs`` causal (query, key) pairs need (module
    docstring), multiply-adds counted as two; ``held_share`` of a token's
    picks land on an expert held here."""
    m, e, a = kinds(hf)
    d, hq, hd = hf["hidden_size"], hf["num_attention_heads"], hf["head_dim"]
    mamba = (2 * (d * (d_inner(hf) + conv_dim(hf)) + d * hf["mamba_num_heads"] + d_inner(hf) * d)
             + 2 * hf["conv_kernel"] * conv_dim(hf) + 6 * d_inner(hf) * hf["ssm_state_size"])
    attn = 2 * (2 * d * hq * hd + 2 * d * hf["num_key_value_heads"] * hd)
    moe = 2 * (_moe_fixed_params(hf) - d
               + held_share * hf["num_experts_per_tok"] * _expert_params(hf))
    return (tokens * (m * mamba + a * attn + e * moe)
            + pairs * a * hq * 4 * hd + rows * 2 * d * hf["vocab_size"])


def expert_call_seconds(src) -> float | None:
    """Mean device seconds of one call of the routed-expert kernel(s) inside
    the burst decode program's launches (a chunk launch calls the grouped
    kernel too, on other rows): an expert layer of a step is one call of
    whichever form the step takes."""
    devs = _device_lines(src)
    if not devs:
        return None
    spans = sorted((s, s + d) for name, s, d in devs[0].get(reduce_trace.MODULES_LINE, [])
                   if "decode" in reduce_trace.program_name(name))
    ds = []
    for name, s, d in devs[0].get(reduce_trace.OPS_LINE, []):
        label, opcode = reduce_trace.op_label(name)
        if opcode == "custom-call" and any(k in label for k in EXPERT_KERNELS) and any(
                lo <= s < hi for lo, hi in spans):
            ds.append(d)
    return sum(ds) / len(ds) / 1e9 if ds else None


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
