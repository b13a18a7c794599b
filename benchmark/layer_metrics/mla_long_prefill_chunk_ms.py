"""Device milliseconds of one chunk launch of a long prompt's prefill
(``prefill_chunk_group``: 2 or 4 prompts' chunks of 256 tokens, and
``prefill1``: one prompt's, through every layer): the device seconds of the
launches that lie wholly inside the traced span over their count, as the
trace reduction's ``programs`` gives both. A launch's time follows the keys
its rows attend over (``live_keys`` of its ``batcher.admit`` record), not the
32,768 of the window the program is built for."""

METRIC = {"name": "mla_long_prefill_chunk_ms", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "model step", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib import roofline_mla_plain as rl

    dev = rl.chunk_launches(src) if rl.is_family(src["config"]) else None
    return 1e3 * dev[0] / dev[1] if dev else None
