"""Imbalance of the routed experts in decode: the most rows any one expert
received in a step and layer, over the rows an expert would receive if the
picks were spread evenly (live rows x experts per token / experts), both
averaged over the window's steps and expert layers. 1 is a perfect spread;
experts / experts per token is every row on the same experts. A dropless
layer's slowest expert sets its time once experts are computed apart, so
this is the tail a grouped expert matmul will feel. Counters as for
``moe_experts_hit_avg``."""

METRIC = {"name": "moe_expert_rows_max_over_mean", "unit": "ratio", "better": "lower",
          "source": "program_counter", "layer": "model step", "moves": "gap_p95_ms"}


def read(src):
    from benchmark.lib.roofline_mla_moe import window_moe_counters

    c = window_moe_counters(src)
    if not c or not c["expert_rows"]:
        return None
    hf = src["config"]
    mean = c["expert_rows"] * hf["num_experts_per_tok"] / hf["n_routed_experts"]
    return c["expert_rows_max"] / mean
