"""Rows that hold a request, a decode step of the window, in the lightning /
block-sparse family: ``state_rows`` / ``state_steps`` of the
``batcher.readback`` spans of the decode bursts read back inside it (the
program's own count of the live rows of each burst)."""

METRIC = {"name": "sala_rows_live_avg", "unit": "rows/step", "better": "higher",
          "source": "program_counter", "layer": "batcher", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib import roofline_sala as rl

    c = rl.window_bursts(src) if rl.is_family(src["config"]) else None
    return rl.step_means(c)[0] if c else None
