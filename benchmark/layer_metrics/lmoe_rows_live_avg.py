"""Rows whose recurrent state a decode step advanced, a step, over the window,
in the state-space family with layers of latent experts: ``state_rows`` /
``state_steps`` of the ``batcher.readback`` spans of the decode bursts read
back inside it (the program's own count of the live rows of each burst, of 64
slots)."""

METRIC = {"name": "lmoe_rows_live_avg", "unit": "rows/step", "better": "higher",
          "source": "program_counter", "layer": "batcher", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib import roofline_ssm_latent_moe as rl

    c = rl.window_bursts(src) if rl.is_family(src["config"]) else None
    return rl.step_means(c)[1] if c else None
