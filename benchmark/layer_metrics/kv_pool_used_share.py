"""Mean share of the paged KV pool's blocks that were live, sampled each
second of the window (the counts behind the ``lmstudio_kv_pool_*`` gauges,
``BlockPool.stats()``)."""

METRIC = {"name": "kv_pool_used_share", "unit": "%", "better": "higher",
          "source": "program_counter", "layer": "KV pool", "moves": "out_tok_s"}


def read(src):
    shares = [100.0 * s["pool"]["blocks_live"] / s["pool"]["blocks_total"]
              for s in src["samples"] if s.get("pool") and s["pool"]["blocks_total"]]
    return sum(shares) / len(shares) if shares else None
