"""Median wait between a request's enqueue and its admit into a slot, from
the span waterfall the worker puts into each reply's ``stats.trace``
(``obs/trace.py``: ``queue_ms`` = enqueue -> admit), over the requests sent
inside the window."""

METRIC = {"name": "admit_wait_p50_ms", "unit": "ms", "better": "lower",
          "source": "program_span", "layer": "batcher", "moves": "ttft_p50_ms"}


def read(src):
    from benchmark.lib.stats import percentile

    w0, w1 = src["window"]
    waits = [r.stats["trace"]["spans_ms"]["queue_ms"] for r in src["records"]
             if w0 <= r.t_sent < w1 and r.stats
             and "queue_ms" in r.stats.get("trace", {}).get("spans_ms", {})]
    return percentile(waits, 0.5) if waits else None
