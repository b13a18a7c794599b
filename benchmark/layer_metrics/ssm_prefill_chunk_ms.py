"""Device milliseconds of one prefill launch of a model of state-space layers
beside attention layers (a chunk of 256 tokens of 1 to 8 prompts through all
40 layers: the chunked scan, the convolution, four layers of attention): the
device seconds of the family's prefill programs' launches that lie wholly
inside the traced span over their count, as the trace reduction's
``programs`` gives both (``prefill_chunk_group``, ``prefill1`` and the fused
admits). Nothing to read where the span holds no whole launch of them."""

METRIC = {"name": "ssm_prefill_chunk_ms", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "model step", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib import roofline_ssm_hybrid as rl

    if "layer_types" not in src["config"]:
        return None
    found = rl.prefill_launches(src)
    return 1e3 * found[0] / found[1] if found else None
