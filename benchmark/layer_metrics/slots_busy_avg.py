"""Tokens the batcher delivered per decode step over the window
(``BatcherStats`` delta tokens / delta steps): how many slots were really
generating, speculation's extra tokens included."""

METRIC = {"name": "slots_busy_avg", "unit": "tokens/step", "better": "higher",
          "source": "program_counter", "layer": "batcher", "moves": "out_tok_s"}


def read(src):
    a, b = src["stats_before"], src["stats_after"]
    steps = b["steps"] - a["steps"]
    return (b["tokens"] - a["tokens"]) / steps if steps > 0 else None
