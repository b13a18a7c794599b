"""A second per-layer reader, added as a file only: requests completed in
the window (shows that the harness finds readers by name)."""

METRIC = {"name": "toy_requests_done", "unit": "requests", "better": "higher",
          "source": "host_clock", "layer": "load generator", "moves": "ttft_p50_ms"}


def read(src):
    return float(src["client"]["completed"])
