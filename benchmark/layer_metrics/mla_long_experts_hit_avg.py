"""Distinct experts the live rows of a decode step hit, averaged over the
window's steps and expert layers, in the plain latent-attention family: the
hit list's length, i.e. what a step READS of each layer's 128 experts (9.4 MB
each), and the number that says whether two seeds did the same work (the
cell's router is silent and the selection bias alone picks: 6.0 on every
seed). From the ``experts_hit`` / ``expert_steps`` of the window's
``batcher.readback`` spans."""

METRIC = {"name": "mla_long_experts_hit_avg", "unit": "experts/step", "better": "lower",
          "source": "program_counter", "layer": "model step", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib import roofline_mla_plain as rl

    c = rl.window_bursts(src) if rl.is_family(src["config"]) else None
    return c["experts_hit"] / c["expert_steps"] if c else None
