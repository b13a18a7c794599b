"""A decode step's share of its roofline in the lightning / block-sparse
family: the bytes one step must move (every layer's weights once with the
head, the live rows' state of every lightning layer once in and once out, the
keys and values of the keys the rows' picks hold and the pooled keys they can
see in every sparse layer: ``benchmark/lib/roofline_sala.py``, all from the
run's own counters of the bursts read back inside the TRACED SPAN) over the
published bandwidth, against the device seconds of one step of the burst
decode program (launches wholly inside the traced span)."""

METRIC = {"name": "sala_decode_step_roofline", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib import roofline_sala as rl

    if not rl.is_family(src["config"]):
        return None
    c, step_s = rl.span_bursts(src), rl.decode_step_seconds(src)
    if not c or not step_s:
        return None
    return 100.0 * rl.decode_step_bytes(src["config"], *rl.step_means(c)) / rl.bandwidth(src) / step_s
