"""Median prefill time of an admit as the batcher times it
(``BatcherStats.prefill_ms`` histogram, window delta; bucketed x1.25)."""

METRIC = {"name": "prefill_p50_ms", "unit": "ms", "better": "lower",
          "source": "program_span", "layer": "model step", "moves": "ttft_p50_ms"}


def read(src):
    from benchmark.lib.stats import hist_delta, hist_percentile

    return hist_percentile(hist_delta(src["stats_before"]["hist"]["prefill_ms"],
                                      src["stats_after"]["hist"]["prefill_ms"]), 0.5)
