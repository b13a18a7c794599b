"""The ring kernel's share of its roofline (``window_decode_attention``, one
call a window layer a step): the bytes a call must read (the last
min(context, 512) keys and values of every LIVE row, 4,096 B a token:
``benchmark/lib/roofline_swa_moe.py``) over the published bandwidth, against
the mean device seconds of a call in the trace. Bandwidth-bound: 8 query
heads share every byte read. The kernel reads all 16 slots' rings whether a
slot holds a request or not, so at 13 live rows it cannot pass 81 %. The live
rows are the traced span's own bursts'."""

METRIC = {"name": "swa_window_kernel_roofline", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels", "moves": "gap_p95_ms"}


def read(src):
    from benchmark.lib import roofline_swa_moe as rl

    if not rl.is_family(src["config"]):
        return None
    ds, c = rl.kernel_durations_ns(src, rl.WINDOW_KERNEL), rl.span_counters(src)
    if not ds or not c:
        return None
    call_s = sum(ds) / len(ds) / 1e9
    need = rl.kernel_call_bytes(src["config"], c["win_tokens"] / c["win_steps"])
    return 100.0 * need / rl.bandwidth(src) / call_s
