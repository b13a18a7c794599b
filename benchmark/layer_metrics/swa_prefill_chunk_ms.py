"""Device milliseconds of one prefill launch of a model of window layers
beside full layers with routed experts (a chunk of 256 tokens of 1 to 4
prompts through all 5 layers: the flash chunk kernel over the full layers'
row cache, the window layers' attention over ring + chunk in plain XLA, the
grouped expert kernel): the device seconds of the family's prefill programs'
launches that lie wholly inside the traced span over their count, as the
trace reduction's ``programs`` gives both (``prefill_chunk_group``,
``prefill1`` and the fused admits). Nothing to read where the span holds no
whole launch of them."""

METRIC = {"name": "swa_prefill_chunk_ms", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "model step", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib import roofline_swa_moe as rl

    if not rl.is_family(src["config"]):
        return None
    found = rl.prefill_launches(src)
    return 1e3 * found[0] / found[1] if found else None
