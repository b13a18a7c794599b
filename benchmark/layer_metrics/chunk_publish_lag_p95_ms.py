"""How long a chunk waits between the batcher's owner thread handing over
its last token (``req.emit``) and the worker's ``nc.publish`` of the chunk
returning on the event-loop thread: ``lag_ms`` of the ``worker.publish``
spans that ended inside the window, 95th percentile."""

METRIC = {"name": "chunk_publish_lag_p95_ms", "unit": "ms", "better": "lower",
          "source": "program_span", "layer": "worker", "moves": "gap_p95_ms"}


def read(src):
    from benchmark.lib.spans import window_records
    from benchmark.lib.stats import percentile

    w0, w1 = src["window"]
    lags = [r[3]["lag_ms"] for r in window_records(src, "worker.publish") or []
            if w0 <= r[2] < w1 and r[3] and "lag_ms" in r[3]]
    return percentile(lags, 0.95) if lags else None
