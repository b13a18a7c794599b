"""Device milliseconds of one chunk launch of the state-space family with
layers of latent experts (256 tokens of 1 to 4 prompts through every layer: the
chunked scan at 8 groups, the convolution, attention over the row cache, the
grouped experts held here between the latent pair): the device seconds of
``prefill_chunk_group`` and ``prefill1`` launches that lie wholly inside the
traced span over their count. Nothing to read where the span holds no whole
launch of them."""

METRIC = {"name": "lmoe_prefill_chunk_ms", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "model step", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib import roofline_ssm_latent_moe as rl

    if not rl.is_family(src["config"]):
        return None
    found = rl.chunk_launches(src)
    return 1e3 * found[0] / found[1] if found else None
