"""Bytes, operations and seconds of the plain latent-attention / routed-expert
family (``references/mla_moe_plain.py``: one residual stream, one query
matrix, ``n_shared_experts`` shared experts), from shapes, and what the
program's spans, counters and the device trace give the ``mla_long_*``
readers. Peaks are ``lib/roofline.py``'s; the trace helpers are
``lib/roofline_mla_moe.py``'s. The byte and operation functions read only keys
this family's configurations have (no ``q_lora_rank``, no ``hc_mult``).
Everything returns ``None`` where the program has no such span, counter or
kernel (a parent commit, another family): the reader then returns ``None``
and the metric is left out of the line.

What a decode step reads, whatever the kernels (bf16, 2 bytes a number):
every weight outside the routed experts once with the head (the embedding
table is read by rows), each routed expert that a live row picked once, and
the latent and the rotary key of every LIVE TOKEN in every layer: 512 + 64
numbers, not the 128 lanes the rotary pool pads its rows to, and not blocks
or windows. What a chunk launch must compute at least: the projections and
the FFN of its real tokens, each (row, pick) pair's expert, one head row a
prompt row, causal attention over the (query, key) pairs its rows really
have in the expanded form (qk 192 / v 128 a head), and the expansion of the
chunk's OWN latents to keys and values (a flash kernel would still have to
expand each latent once; what the blocked XLA form expands again for every
later chunk is the program's cost, not the model's).

The rows, contexts and experts a device time is priced against are the TRACED
SPAN's own (``batcher.readback`` spans of decode bursts with ``live_tokens``,
``batcher.admit`` records of chunk launches with ``tokens`` / ``pairs``), not
the window's mean (PERF.md, PR 36; ROADMAP B1).
"""

from __future__ import annotations

from benchmark.lib.roofline import peaks
from benchmark.lib.roofline_mla_moe import (  # noqa: F401 — the readers' one import
    bandwidth, decode_step_seconds, kernel_durations_ns)
from benchmark.lib.spans import traced_span, window_records

CHUNK_PROGRAMS = ("prefill_chunk_group", "prefill1")
BURST_KEYS = ("live_tokens", "experts_hit", "expert_rows", "expert_steps")
CHUNK_KEYS = ("rows", "tokens", "pairs")


def is_family(hf: dict) -> bool:
    return "kv_lora_rank" in hf and not hf.get("q_lora_rank") and "hc_mult" not in hf


def moe_layers(hf: dict) -> int:
    return hf["num_hidden_layers"] - hf["first_k_dense_replace"]


def _attn_params(hf: dict) -> int:
    d, hq = hf["hidden_size"], hf["num_attention_heads"]
    dn, dr, dv, r = (hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"],
                     hf["kv_lora_rank"])
    return d * hq * (dn + dr) + d * (r + dr) + r * hq * (dn + dv) + hq * dv * d


def _expert_params(hf: dict) -> int:
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def _moe_fixed_params(hf: dict) -> int:
    """Router, selection bias and the shared experts of one expert layer."""
    return (hf["hidden_size"] * hf["n_routed_experts"] + hf["n_routed_experts"]
            + hf["n_shared_experts"] * _expert_params(hf))


def param_count(hf: dict) -> int:
    """Every parameter of the tree the program serves (head untied)."""
    d, L = hf["hidden_size"], hf["num_hidden_layers"]
    layer = _attn_params(hf) + 2 * d + hf["kv_lora_rank"]
    dense = 3 * d * hf["intermediate_size"]
    moe = _moe_fixed_params(hf) + hf["n_routed_experts"] * _expert_params(hf)
    head = d * hf["vocab_size"] * (1 if hf.get("tie_word_embeddings") else 2)
    return L * layer + hf["first_k_dense_replace"] * dense + moe_layers(hf) * moe + d + head


def cache_token_bytes(hf: dict) -> float:
    """Cached bytes a token a layer: the latent and the shared rotary key."""
    return 2.0 * (hf["kv_lora_rank"] + hf["qk_rope_head_dim"])


def expert_bytes(hf: dict) -> float:
    return 2.0 * _expert_params(hf)


def non_expert_weight_bytes(hf: dict) -> float:
    """Every weight a decode step reads whole, outside the routed experts."""
    d, L = hf["hidden_size"], hf["num_hidden_layers"]
    layer = _attn_params(hf) + 2 * d + hf["kv_lora_rank"]
    return 2.0 * (L * layer + hf["first_k_dense_replace"] * 3 * d * hf["intermediate_size"]
                  + moe_layers(hf) * _moe_fixed_params(hf) + d + d * hf["vocab_size"])


def decode_step_bytes(hf: dict, experts_hit_per_layer: float, live_tokens: float,
                      rows: float) -> float:
    return (non_expert_weight_bytes(hf)
            + moe_layers(hf) * experts_hit_per_layer * expert_bytes(hf)
            + hf["num_hidden_layers"] * live_tokens * cache_token_bytes(hf)
            + 2.0 * rows * hf["hidden_size"])


def kernel_call_bytes(hf: dict, live_tokens: float) -> float:
    """What one call of the absorbed kernel (one layer, every slot) must read."""
    return live_tokens * cache_token_bytes(hf)


def chunk_min_flops(hf: dict, rows: float, tokens: float, pairs: float) -> float:
    """The least operations chunk launches of ``rows`` prompt rows, ``tokens``
    real tokens and ``pairs`` causal (query, key) pairs need (module
    docstring), multiply-adds counted as two."""
    d, L, hq = hf["hidden_size"], hf["num_hidden_layers"], hf["num_attention_heads"]
    dn, dr, dv = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"]
    per_token = (L * _attn_params(hf)   # the projections; W_ukv here is the chunk's own expansion
                 + hf["first_k_dense_replace"] * 3 * d * hf["intermediate_size"]
                 + moe_layers(hf) * (d * hf["n_routed_experts"]
                                     + (hf["n_shared_experts"] + hf["num_experts_per_tok"])
                                     * _expert_params(hf)))
    attention = L * hq * (dn + dr + dv)   # a pair: the score and the value, every head
    return 2.0 * (tokens * per_token + pairs * attention + rows * d * hf["vocab_size"])


def _bursts(src, lo: float, hi: float) -> dict | None:
    """The decode bursts read back in [lo, hi) that carry ``live_tokens``,
    summed, with their count."""
    tot = dict.fromkeys(BURST_KEYS, 0) | {"bursts": 0}
    for _, _, t1, attrs in window_records(src, "batcher.readback") or []:
        if attrs and "live_tokens" in attrs and attrs.get("expert_steps") and lo <= t1 < hi:
            for k in BURST_KEYS:
                tot[k] += attrs[k]
            tot["bursts"] += 1
    return tot if tot["bursts"] else None


def window_bursts(src) -> dict | None:
    return _bursts(src, *src["window"])


def span_bursts(src) -> dict | None:
    return _bursts(src, *traced_span(src))


def step_means(hf: dict, c: dict) -> tuple[float, float, float]:
    """(experts hit a layer, live rows, live tokens) of a mean step of the
    bursts ``c`` sums: a burst's ``live_tokens`` are its rows' positions at
    its first step; a row grows a token a step and reads its own new one."""
    layer_steps = c["expert_steps"]
    steps = layer_steps / moe_layers(hf)
    rows = c["expert_rows"] / layer_steps
    per_burst = steps / c["bursts"]
    live = c["live_tokens"] / c["bursts"] + rows * (per_burst + 1) / 2.0
    return c["experts_hit"] / layer_steps, rows, live


def span_chunks(src) -> list[dict] | None:
    """The ``batcher.admit`` records of the chunk launches that began inside
    the traced span, oldest first."""
    lo, hi = traced_span(src)
    out = [a for _, t0, _, a in window_records(src, "batcher.admit") or []
           if a and a.get("program") == "chunk" and "pairs" in a and lo <= t0 < hi]
    return out or None


def chunk_launches(src) -> tuple[float, float] | None:
    """(device seconds, launches) of the chunk programs wholly inside the trace."""
    programs = src["trace"].get("programs", {})
    found = [programs[p] for p in CHUNK_PROGRAMS if programs.get(p, {}).get("launches")]
    if not found:
        return None
    return sum(p["seconds"] for p in found), sum(p["launches"] for p in found)


def chunk_mfu(src) -> float | None:
    """The chunk launches' share of the chip's bf16 peak, in per cent: the
    least operations of a mean launch of the span's own records, times the
    launches the trace holds whole, over their device seconds."""
    chunks, dev = span_chunks(src), chunk_launches(src)
    if not chunks or not dev:
        return None
    seconds, launches = dev
    tot = {k: sum(a[k] for a in chunks) for k in CHUNK_KEYS}
    need = chunk_min_flops(src["config"], tot["rows"], tot["tokens"], tot["pairs"]) / len(chunks)
    peak = peaks(src["device"]["kind"])["bf16_flops_per_s"] * src["device"]["count"]
    return 100.0 * need * launches / seconds / peak


def chunk_scope_share(src, scope: str = "seq/mla") -> float | None:
    """Per cent of the chunk launches' device time under ``scope``."""
    from benchmark.lib import scopes

    t = scopes.table(src)
    if t is None:
        return None
    total = sum(l["ns"] for p, l in t["launches"].items() if p in CHUNK_PROGRAMS)
    under = sum(ns for (p, s), (ns, _) in t["ops"].items()
                if p in CHUNK_PROGRAMS and s and (s == scope or s.startswith(scope + "/")))
    return 100.0 * under / total if total and under else None
