"""Distinct experts the live rows of a decode step hit, averaged over the
window's steps and expert layers: what a step would read of each layer's
routed experts if it read only those hit (dense dispatch reads all of them).
From the program's own counters (``models/mla_moe.py`` counts on the device,
``decode_pos_moe`` returns them in the burst's readback, ``BatcherStats``
sums them): each burst's sums ride its ``batcher.readback`` span's attrs, and
the bursts read back inside the window are added up here. A program without
the counters (a parent commit, a family without expert layers) gives
nothing."""

METRIC = {"name": "moe_experts_hit_avg", "unit": "experts/step", "better": "lower",
          "source": "program_counter", "layer": "model step", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib.roofline_mla_moe import window_moe_counters

    c = window_moe_counters(src)
    return c["experts_hit"] / c["expert_steps"] if c else None
