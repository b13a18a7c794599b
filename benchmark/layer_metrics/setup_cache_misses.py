"""Programs that asked the persistent compile cache during set-up and were
compiled anyway (the build ledger's requests less its hits, when the window
began): 1 on a warm cache, every program on an empty one or after a change
to what the programs hold."""

METRIC = {"name": "setup_cache_misses", "unit": "count", "better": "lower",
          "source": "program_counter", "layer": "batcher", "moves": "setup_s"}


def read(src):
    from benchmark.lib.spans import ledger_at_window_start

    ledger = ledger_at_window_start(src)
    return ledger["requests"] - ledger["hits"] if ledger else None
