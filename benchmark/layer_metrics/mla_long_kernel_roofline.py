"""The absorbed latent-attention decode kernel's share of its roofline at long
contexts: the bytes one call must read (the latent and the rotary key of
every live token of the step, ``benchmark/lib/roofline_mla_plain.py``) over
the published bandwidth, against the mean device seconds of a call in the
trace. Bandwidth-bound: 32 heads share every byte read. The live tokens are
those of the traced span's own bursts (the ``live_tokens`` of their
``batcher.readback`` spans, grown by the burst's steps), not pool blocks and
not the window's mean."""

METRIC = {"name": "mla_long_kernel_roofline", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels", "moves": "gap_p95_ms"}


def read(src):
    from benchmark.lib import roofline_mla_plain as rl

    if not rl.is_family(src["config"]):
        return None
    ds, c = rl.kernel_durations_ns(src), rl.span_bursts(src)
    if not ds or not c:
        return None
    _, _, live = rl.step_means(src["config"], c)
    call_s = sum(ds) / len(ds) / 1e9
    return 100.0 * rl.kernel_call_bytes(src["config"], live) / rl.bandwidth(src) / call_s
