"""Peaks of the chips the benchmark may run on, and the bytes and operations
a decode step needs, computed from shapes. The benchmark's own table: the
program's ``obs/roofline.py`` is not read."""

from __future__ import annotations

# Published per-chip peaks, keyed by jax's ``device_kind``. Source: Google
# Cloud documentation, "TPU v5e" system architecture: 197 TFLOP/s bf16,
# 393 TOP/s int8, 16 GB HBM2e at 819 GB/s. A kind that is not here is an
# error, never a default.
PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
    "TPU v5e": {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
                "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       "add it to benchmark/lib/roofline.py with its source") from None


def _geometry(hf: dict) -> tuple[int, int, int, int, int, int, int]:
    d = hf["hidden_size"]
    hq, hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf.get("head_dim") or d // hq
    return (hf["num_hidden_layers"], d, hf["intermediate_size"], hq * hd, hkv * hd,
            hf["vocab_size"], hd)


def decode_step_bytes(hf: dict, wquant: str, kv_bytes_per_value: float,
                      live_kv_tokens: float, rows: float) -> float:
    """Bytes one decode step has to read from HBM, whatever the kernels:
    every block matrix and the head once (int8 codes + one f32 scale per
    output channel, or bf16), the norms, one embedding row per live row, and
    the keys and values of every live token in every layer."""
    L, d, ff, hq, hkv, v, _ = _geometry(hf)
    wb = 1.0 if wquant == "int8" else 2.0
    per_layer_out = hq + 2 * hkv + d + 2 * ff + d      # output channels
    per_layer_w = d * hq + 2 * d * hkv + hq * d + 3 * d * ff
    scales = 4.0 * (L * per_layer_out + v) if wquant == "int8" else 0.0
    weights = wb * (L * per_layer_w + d * v) + scales
    norms = 2.0 * (2 * L * d + d)
    embed_rows = 2.0 * rows * d
    kv = 2.0 * L * hkv * kv_bytes_per_value * live_kv_tokens
    return weights + norms + embed_rows + kv


def decode_step_flops(hf: dict, live_kv_tokens: float, rows: float) -> float:
    """Operations one decode step needs: 2 per weight per row, plus the
    score and value products over the live keys (per row, the row's own
    context; ``live_kv_tokens`` is the sum over rows)."""
    L, d, ff, hq, hkv, v, _ = _geometry(hf)
    per_layer_w = d * hq + 2 * d * hkv + hq * d + 3 * d * ff
    return 2.0 * rows * (L * per_layer_w + d * v) + 4.0 * L * hq * live_kv_tokens


def decode_step_bound_s(hf: dict, device_kind: str, wquant: str,
                        kv_bytes_per_value: float, live_kv_tokens: float,
                        rows: float, chips: int = 1) -> dict:
    """The least time the chips could take for one decode step: the larger
    of bytes over bandwidth and operations over peak (bf16 peak: int8 weights
    are multiplied as bf16), and which of the two binds."""
    pk = peaks(device_kind)
    by = decode_step_bytes(hf, wquant, kv_bytes_per_value, live_kv_tokens, rows)
    fl = decode_step_flops(hf, live_kv_tokens, rows)
    t_b = by / (pk["hbm_bytes_per_s"] * chips)
    t_f = fl / (pk["bf16_flops_per_s"] * chips)
    return {"bytes": by, "flops": fl, "bound_s": max(t_b, t_f),
            "bound_by": "bandwidth" if t_b >= t_f else "compute"}
