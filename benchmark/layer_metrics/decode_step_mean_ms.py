"""Mean time of one decode step over the window: sum over count of the
``BatcherStats.decode_step_ms`` histogram's window delta (exact, unlike the
bucketed median)."""

METRIC = {"name": "decode_step_mean_ms", "unit": "ms", "better": "lower",
          "source": "program_span", "layer": "model step", "moves": "gap_p95_ms"}


def read(src):
    from benchmark.lib.stats import hist_delta, hist_mean

    return hist_mean(hist_delta(src["stats_before"]["hist"]["decode_step_ms"],
                                src["stats_after"]["hist"]["decode_step_ms"]))
