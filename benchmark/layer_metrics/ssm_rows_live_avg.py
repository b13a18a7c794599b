"""Rows whose recurrent state a decode step advanced, a step, over the window:
``state_rows`` / ``state_steps`` of the ``batcher.readback`` spans of the
decode bursts read back inside it (the program's own count of the live rows
of each burst, ``BatcherStats.record_state``)."""

METRIC = {"name": "ssm_rows_live_avg", "unit": "rows/step", "better": "higher",
          "source": "program_counter", "layer": "batcher", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib import roofline_ssm_hybrid as rl

    return rl.live_rows(src)
