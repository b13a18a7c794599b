"""The owner loop's period: start to start of consecutive
``batcher.dispatch`` spans (one per decode burst, masked step or verify)
that began inside the window, median. Against the device seconds of one
burst it says what else the loop waited for between two bursts."""

METRIC = {"name": "burst_period_p50_ms", "unit": "ms", "better": "lower",
          "source": "program_span", "layer": "batcher", "moves": "gap_p95_ms"}


def read(src):
    from benchmark.lib.spans import window_records
    from benchmark.lib.stats import percentile

    w0, w1 = src["window"]
    starts = sorted(r[1] for r in window_records(src, "batcher.dispatch") or []
                    if w0 <= r[1] < w1)
    periods = [(b - a) * 1e3 for a, b in zip(starts, starts[1:])]
    return percentile(periods, 0.5) if periods else None
