"""The chunk launches' share of the chip's bf16 peak in the lightning /
block-sparse family: the least operations a mean chunk launch of the traced
span must compute (``benchmark/lib/roofline_sala.py chunk_min_flops``: the
projections and the SwiGLU of its real tokens, the recurrence a token at a
time, the scores against the pooled keys its queries can see, attention over
the (query, key) pairs their picks allow, one head row a prompt row; from the
``batcher.admit`` records of the span's own chunk launches) times the launches
the trace holds whole, over their device seconds and the published peak."""

METRIC = {"name": "sala_prefill_chunk_mfu", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "model step", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib import roofline_sala as rl

    return rl.chunk_mfu(src) if rl.is_family(src["config"]) else None
