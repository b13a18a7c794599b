"""Median gap between consecutive chunks of one stream as the client sees
them, pooled over all streams: one undisturbed decode burst. The steadier
companion of ``gap_p95_ms``, whose 95th percentile sits where the gaps that
waited out an admit begin and so reads 3-4 % high in about one run of eight."""

METRIC = {"name": "gap_p50_ms", "unit": "ms", "better": "lower",
          "source": "host_clock", "layer": "model step", "moves": "gap_p95_ms"}


def read(src):
    gap = src["client"].get("gap_p50_s")
    return gap * 1e3 if gap is not None and gap == gap else None
