"""The host's headroom: the share of the window that the batcher's owner
thread spent waiting, blocked on a readback from the device
(``batcher.readback``) or on its inbox (``batcher.intake``). Near 100 %
the device sets the pace; what is missing from 100 % is host work between
bursts that the device may or may not be hiding."""

METRIC = {"name": "owner_wait_share", "unit": "%", "better": "higher",
          "source": "program_span", "layer": "batcher", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib.spans import window_records

    w0, w1 = src["window"]
    recs = [r for name in ("batcher.readback", "batcher.intake")
            for r in window_records(src, name) or []]
    if not recs or w1 <= w0:
        return None
    waited = sum(max(0.0, min(t1, w1) - max(t0, w0)) for _, t0, t1, _ in recs)
    return 100.0 * waited / (w1 - w0)
