"""Device milliseconds a decode step spends in the absorbed latent-attention
kernel at long contexts: the mean device time of a ``custom-call`` named
``mla_paged_decode_attention`` in the trace, times the model's layers (one
call a layer a step, every slot in it). Its bytes follow the live tokens
(``mla_long_kernel_roofline`` prices them at the traced span's own)."""

METRIC = {"name": "mla_long_kernel_ms_per_step", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "kernels", "moves": "gap_p95_ms"}


def read(src):
    from benchmark.lib import roofline_mla_plain as rl

    if not rl.is_family(src["config"]):
        return None
    ds = rl.kernel_durations_ns(src)
    if not ds:
        return None
    return src["config"]["num_hidden_layers"] * sum(ds) / len(ds) / 1e6
