"""Median time of one decode step as the batcher times it (host clock
around a burst that ends in a readback, divided by the burst's steps):
``BatcherStats.decode_step_ms`` histogram, window delta. Bucketed x1.25, so
good to about a tenth; ``decode_step_mean_ms`` is exact."""

METRIC = {"name": "decode_step_p50_ms", "unit": "ms", "better": "lower",
          "source": "program_span", "layer": "model step", "moves": "gap_p95_ms"}


def read(src):
    from benchmark.lib.stats import hist_delta, hist_percentile

    return hist_percentile(hist_delta(src["stats_before"]["hist"]["decode_step_ms"],
                                      src["stats_after"]["hist"]["decode_step_ms"]), 0.5)
