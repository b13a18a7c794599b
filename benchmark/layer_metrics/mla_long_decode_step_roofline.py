"""The decode step's share of its roofline in the plain latent-attention /
routed-expert family at long contexts: the bytes one step must read (every
weight outside the routed experts with the head, the experts the live rows
HIT by the program's counter, the latent and the rotary key of every live
token in every layer: ``benchmark/lib/roofline_mla_plain.py``) over the
published bandwidth, against the device seconds of one step of the burst
decode program (launches wholly inside the traced span). Rows, experts hit
and live tokens are the traced span's own bursts' (``live_tokens`` of their
``batcher.readback`` spans), not the window's mean. With 6 of 48 layers the
head is 0.53 of the ~1.0 GB a step reads outside the experts, a larger share
than in a deployment."""

METRIC = {"name": "mla_long_decode_step_roofline", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib import roofline_mla_plain as rl

    if not rl.is_family(src["config"]):
        return None
    c, step_s = rl.span_bursts(src), rl.decode_step_seconds(src)
    if not c or not step_s:
        return None
    hit, rows, live = rl.step_means(src["config"], c)
    return 100.0 * rl.decode_step_bytes(src["config"], hit, live, rows) / rl.bandwidth(src) / step_s
