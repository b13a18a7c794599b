"""The absorbed latent-attention decode kernel's share of its roofline: the
bytes one call must read (the latent and the rotary key of every live token,
``benchmark/lib/roofline_mla_moe.py``) over the published bandwidth, against
the mean device seconds of a call in the trace. Bandwidth-bound: 32 heads
share every byte read. Live tokens are the pool's live blocks x block size
over the window's samples (blocks the prefix cache alone holds are counted
too: at most 64 of ~1,000)."""

METRIC = {"name": "mla_kernel_roofline", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels", "moves": "gap_p95_ms"}


def read(src):
    from benchmark.lib import roofline_mla_moe as rl

    ds, live = rl.kernel_durations_ns(src), rl.live_tokens(src)
    if not ds or not live:
        return None
    call_s = sum(ds) / len(ds) / 1e9
    return 100.0 * rl.kernel_call_bytes(src["config"], live) / rl.bandwidth(src) / call_s
