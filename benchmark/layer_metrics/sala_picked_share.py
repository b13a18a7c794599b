"""Per cent of the keys the window's decoding rows could see in the
block-sparse layers that their picked walks read: ``sparse_tokens_picked`` /
``sparse_tokens_live`` of the ``batcher.readback`` spans of the decode bursts
read back inside the window (``BatcherStats.record_sparse``: a row under the
dense length walks all it sees; past it, ``topk`` blocks). What the
architecture promises falls as contexts grow: 4,096 of 18,000 keys is 23."""

METRIC = {"name": "sala_picked_share", "unit": "%", "better": "lower",
          "source": "program_counter", "layer": "model step", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib import roofline_sala as rl

    c = rl.window_bursts(src) if rl.is_family(src["config"]) else None
    return 100.0 * c["sparse_tokens_picked"] / c["sparse_tokens_live"] if c else None
