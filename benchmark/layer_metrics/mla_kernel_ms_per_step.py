"""Device milliseconds of the absorbed latent-attention decode kernel per
decode step: the mean duration of the trace's ``custom-call`` operations
that carry the kernel's fixed name (``ops/mla_attention.py``:
``name="mla_paged_decode_attention"``) times the layers of the model (a step
calls it once a layer). Counting the kernel's own events needs no count of
launches, so a launch cut by the edge of the traced span moves nothing."""

METRIC = {"name": "mla_kernel_ms_per_step", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "kernels", "moves": "gap_p95_ms"}


def read(src):
    from benchmark.lib.roofline_mla_moe import kernel_durations_ns

    ds = kernel_durations_ns(src)
    if not ds:
        return None
    return sum(ds) / len(ds) / 1e6 * src["config"]["num_hidden_layers"]
