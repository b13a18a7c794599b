"""Distinct experts the live rows of a decode step hit, averaged over the
window's steps and expert layers, in the window / full attention family: the
hit list's length, i.e. what a step READS of each layer's 256 experts, and
the number that says whether two seeds did the same work. Read as
``moe_experts_hit_avg`` reads it (the same counters of the same expert layer,
``models/experts.py``, through ``window_moe_counters``), under a name of this
cell's so that the accepted entry's list stays as it is."""

METRIC = {"name": "swa_experts_hit_avg", "unit": "experts/step", "better": "lower",
          "source": "program_counter", "layer": "model step", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib.roofline_swa_moe import is_family, window_moe_counters

    if not is_family(src["config"]):
        return None
    c = window_moe_counters(src)
    return c["experts_hit"] / c["expert_steps"] if c else None
