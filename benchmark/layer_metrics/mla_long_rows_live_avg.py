"""Live rows of a decode step, averaged over the window's steps: ``expert_rows``
/ ``expert_steps`` of the window's ``batcher.readback`` spans (the program's
own count of the slots that hold a request, a layer a step). Of 16 slots:
the others wait for, or are in, a long prompt's chunked prefill."""

METRIC = {"name": "mla_long_rows_live_avg", "unit": "rows/step", "better": "higher",
          "source": "program_counter", "layer": "batcher", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib import roofline_mla_plain as rl

    c = rl.window_bursts(src) if rl.is_family(src["config"]) else None
    return c["expert_rows"] / c["expert_steps"] if c else None
