"""Seconds of set-up the program spent building programs, from its own
build ledger (``obs/compile_cache.py``: JAX's trace, lower and
compile-or-load-from-cache duration events) as it stood when the window
began. The part of ``setup_s`` a colder cache or one more program moves."""

METRIC = {"name": "setup_build_s", "unit": "s", "better": "lower",
          "source": "program_counter", "layer": "batcher", "moves": "setup_s"}


def read(src):
    from benchmark.lib.spans import ledger_at_window_start

    ledger = ledger_at_window_start(src)
    return ledger["total_s"] if ledger else None
