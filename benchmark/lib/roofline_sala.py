"""Bytes, operations and seconds of the lightning / block-sparse family
(``references/sala.py``), from shapes and from what a run itself counted, and
what the program's spans, counters and the device trace give the ``sala_*``
readers. Peaks are ``lib/roofline.py``'s; the trace helpers are
``lib/roofline_mla_moe.py``'s and ``lib/roofline_mla_plain.py``'s. Everything
returns ``None`` where the program has no such span, counter or kernel (a
parent commit, another family): the reader then returns ``None`` and the
metric is left out of the line.

The counts are the least ANY implementation of the equations must move or
compute, not what this one does (bf16 weights, keys and values, 2 bytes a
number; the recurrent state float32):

* a decode step reads every layer's weights once with the head (the embedding
  table is read by rows); for every live row the state of every lightning
  layer once in and once out; in every sparse layer the keys and values of the
  keys the row's picks hold (``sparse_tokens_picked``, the program's count:
  all it sees while under the dense length, ``topk`` blocks past it, the
  frontier block up to its own key) and the pooled keys it can see (one every
  ``kernel_stride`` keys), a kv head each;
* a call of the picked walk (one sparse layer) reads the picked keys and
  values of its rows, both kv heads; a call of the state kernel (one lightning
  layer) its live rows' state once in and once out;
* a chunk launch computes the projections and the SwiGLU of its real tokens in
  both kinds of layer, the recurrence a token at a time (a write and a read of
  a [d, d] state a head: 4 operations an element; the chunked form's [C, C]
  products are the program's cost, not the model's), the scores of every query
  against the pooled keys it can see, attention over the (query, key) pairs
  its picks allow (at most ``topk`` blocks a query past the dense length), and
  one head row a prompt row.

The rows and keys a device time is priced against are the TRACED SPAN's own
(``batcher.readback`` spans of decode bursts, ``batcher.admit`` records of
chunk launches), not the window's mean.
"""

from __future__ import annotations

from benchmark.lib.roofline import peaks
from benchmark.lib.roofline_mla_moe import (  # noqa: F401 — the readers' one import
    bandwidth, decode_step_seconds, kernel_durations_ns)
from benchmark.lib.roofline_mla_plain import (  # noqa: F401
    CHUNK_KEYS, chunk_launches, span_chunks)
from benchmark.lib.spans import readback_sums, traced_span

STATE_KERNEL = "lightning_step"
WALK_KERNEL = "paged_decode_attention_picked"
SELECT_SCOPE = "seq/sparse/select"
SEQ_SCOPES = ("seq/linear", "seq/sparse")


def is_family(hf: dict) -> bool:
    return "lightning_nh" in hf and "sparse_config" in hf


def kinds(hf: dict) -> tuple[int, int]:
    """(lightning layers, sparse layers)."""
    sparse = sum(m == "minicpm4" for m in hf["mixer_types"])
    return len(hf["mixer_types"]) - sparse, sparse


def _ffn_params(hf: dict) -> int:
    return 3 * hf["hidden_size"] * hf["intermediate_size"]


def _lightning_params(hf: dict) -> int:
    return 5 * hf["hidden_size"] * hf["lightning_nh"] * hf["lightning_head_dim"] + _ffn_params(hf)


def _sparse_params(hf: dict) -> int:
    d, hq, hkv, hd = (hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"],
                      hf["head_dim"])
    return d * 2 * hq * hd + 2 * d * hkv * hd + hq * hd * d + _ffn_params(hf)


def param_count(hf: dict) -> int:
    """Every parameter of the served tree (norm gains and the decay leaf too)."""
    lin, sparse = kinds(hf)
    d, hd, ld = hf["hidden_size"], hf["head_dim"], hf["lightning_head_dim"]
    gains = lin * (2 * d + 2 * ld + hf["lightning_nh"] * (ld + 1)) + sparse * (2 * d + 2 * hd) + d
    return (lin * _lightning_params(hf) + sparse * _sparse_params(hf) + gains
            + 2 * d * hf["vocab_size"])


def weight_bytes(hf: dict) -> float:
    """Every weight a decode step reads whole: the layers and the head."""
    lin, sparse = kinds(hf)
    return 2.0 * (lin * _lightning_params(hf) + sparse * _sparse_params(hf)
                  + hf["hidden_size"] * hf["vocab_size"])


def state_layer_bytes(hf: dict) -> float:
    """One slot's float32 state of one lightning layer."""
    return 4.0 * hf["lightning_nh"] * hf["lightning_head_dim"] ** 2


def kv_token_bytes(hf: dict) -> float:
    """Keys and values of one token in one sparse layer, both kv heads."""
    return 2.0 * 2 * hf["num_key_value_heads"] * hf["head_dim"]


def pooled_token_bytes(hf: dict) -> float:
    """Pooled keys a token of context in one sparse layer, both kv heads."""
    return 2.0 * hf["num_key_value_heads"] * hf["head_dim"] / hf["sparse_config"]["kernel_stride"]


def state_step_call_bytes(hf: dict, rows: float) -> float:
    return 2.0 * rows * state_layer_bytes(hf)


def picked_walk_call_bytes(hf: dict, picked_tokens: float) -> float:
    """``picked_tokens``: the keys the rows of ONE call (one sparse layer, one
    step) walk, a kv head."""
    return picked_tokens * kv_token_bytes(hf)


def decode_step_bytes(hf: dict, rows: float, picked_tokens: float, live_tokens: float) -> float:
    """``picked_tokens`` / ``live_tokens``: a step's keys walked / visible,
    summed over the sparse layers (the program's counters)."""
    lin, _ = kinds(hf)
    return (weight_bytes(hf) + 2.0 * rows * lin * state_layer_bytes(hf)
            + picked_tokens * kv_token_bytes(hf) + live_tokens * pooled_token_bytes(hf)
            + 2.0 * rows * hf["hidden_size"])


def chunk_min_flops(hf: dict, rows: float, tokens: float, pairs: float, live_keys: float) -> float:
    """The least operations chunk launches of ``rows`` prompt rows, ``tokens``
    real tokens, ``pairs`` causal (query, key) pairs and ``live_keys`` keys
    behind the rows need (module docstring), multiply-adds counted as two."""
    lin, sparse = kinds(hf)
    d, hq, hd = hf["hidden_size"], hf["num_attention_heads"], hf["head_dim"]
    sc = hf["sparse_config"]
    state = hf["lightning_nh"] * hf["lightning_head_dim"] ** 2
    allowed = min(pairs, tokens * sc["topk"] * sc["block_size"])
    seen_pooled = tokens * (live_keys / max(rows, 1.0)) / sc["kernel_stride"]
    return (tokens * (lin * (2 * _lightning_params(hf) + 4 * state)
                      + sparse * 2 * _sparse_params(hf))
            + sparse * hq * hd * (4 * allowed + 2 * seen_pooled)
            + rows * 2 * d * hf["vocab_size"])


def _bursts(src, lo: float, hi: float) -> dict | None:
    """What the decode bursts read back in [lo, hi) counted, summed."""
    c = readback_sums(src, lo, hi)
    return c if c.get("state_steps") and c.get("sparse_tokens_live") else None


def window_bursts(src) -> dict | None:
    return _bursts(src, *src["window"])


def span_bursts(src) -> dict | None:
    return _bursts(src, *traced_span(src))


def step_means(c: dict) -> tuple[float, float, float]:
    """(live rows, keys walked, keys visible) of a mean step of the bursts
    ``c`` sums, the keys summed over the sparse layers."""
    steps = c["state_steps"]
    return (c["state_rows"] / steps, c["sparse_tokens_picked"] / steps,
            c["sparse_tokens_live"] / steps)


def chunk_mfu(src) -> float | None:
    """The chunk launches' share of the chip's bf16 peak, in per cent: the
    least operations of a mean launch of the span's own records, times the
    launches the trace holds whole, over their device seconds."""
    chunks, dev = span_chunks(src), chunk_launches(src)
    if not chunks or not dev:
        return None
    seconds, launches = dev
    tot = {k: sum(a[k] for a in chunks) for k in CHUNK_KEYS + ("live_keys",)}
    need = chunk_min_flops(src["config"], tot["rows"], tot["tokens"], tot["pairs"],
                           tot["live_keys"]) / len(chunks)
    peak = peaks(src["device"]["kind"])["bf16_flops_per_s"] * src["device"]["count"]
    return 100.0 * need * launches / seconds / peak


def _decode_scope_ns(src, scope_names: tuple[str, ...]):
    """(ns under the scopes, ns and launches of the decode programs) of the
    launches wholly inside the traced span."""
    from benchmark.lib import scopes

    t = scopes.table(src)
    if t is None:
        return None
    progs = {p: l for p, l in t["launches"].items() if l["kind"] == "decode"}
    total = sum(l["ns"] for l in progs.values())
    under = sum(ns for (p, s), (ns, _) in t["ops"].items() if p in progs and s and any(
        s == name or s.startswith(name + "/") for name in scope_names))
    if not total or not under:
        return None
    return under, total, sum(l["n"] for l in progs.values())


def decode_scope_share(src, scope_names: tuple[str, ...] = SEQ_SCOPES) -> float | None:
    """Per cent of the decode programs' device time under the scopes."""
    found = _decode_scope_ns(src, scope_names)
    return 100.0 * found[0] / found[1] if found else None


def select_ms_per_step(src) -> float | None:
    """Device ms a decode step spends under ``seq/sparse/select``, all sparse
    layers together."""
    from benchmark.lib import scopes

    found, steps = _decode_scope_ns(src, (SELECT_SCOPE,)), scopes._steps_a_launch(src)
    return found[0] / 1e6 / (found[2] * steps) if found and steps else None
