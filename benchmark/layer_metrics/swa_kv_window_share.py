"""The window layers' share of the keys a decode step attends to, a layer of
each kind: ``win_tokens`` / (``win_tokens`` + ``full_tokens``) of the decode
bursts read back inside the window (``BatcherStats.record_window``: a live
row at position p sees p + 1 keys in a full layer and min(p + 1, 512) in a
window layer; each burst's sums ride its ``batcher.readback`` span). It is
what the ring saves: a window layer that held whole contexts would read the
full layers' share. A program without the counters (a parent commit, a family
without window layers) gives nothing."""

METRIC = {"name": "swa_kv_window_share", "unit": "%", "better": "lower",
          "source": "program_counter", "layer": "KV pool", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib.roofline_swa_moe import window_counters

    c = window_counters(src)
    return 100.0 * c["win_tokens"] / (c["win_tokens"] + c["full_tokens"]) if c else None
