"""The paged decode kernel's share of its roofline in the full layers of a
model that also has window layers (``paged_decode_attention``, one call a full
layer a step, 6 query heads a kv head): the bytes a call must read (the whole
context of every LIVE row, 4,096 B a token:
``benchmark/lib/roofline_swa_moe.py``) over the published bandwidth, against
the mean device seconds of a call in the trace. Bandwidth-bound. The contexts
are the traced span's own bursts' (``full_tokens`` of their
``batcher.readback`` spans)."""

METRIC = {"name": "swa_full_kernel_roofline", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels", "moves": "gap_p95_ms"}


def read(src):
    from benchmark.lib import roofline_swa_moe as rl

    if not rl.is_family(src["config"]):
        return None
    ds, c = rl.kernel_durations_ns(src, rl.FULL_KERNEL), rl.span_counters(src)
    if not ds or not c:
        return None
    call_s = sum(ds) / len(ds) / 1e9
    need = rl.kernel_call_bytes(src["config"], c["full_tokens"] / c["win_steps"])
    return 100.0 * need / rl.bandwidth(src) / call_s
