"""The chunk launches' share of the chip's bf16 peak: the LEAST operations the
traced span's launches need (projections and FFN of their real tokens, each
(row, pick) pair's expert, one head row a prompt row, causal attention over
the (query, key) pairs their rows really have in the expanded form, the
expansion of the chunk's own latents only:
``benchmark/lib/roofline_mla_plain.py chunk_min_flops``, from the ``tokens``,
``rows`` and ``pairs`` of the launches' own ``batcher.admit`` records) over
their device seconds and the published peak. What the program computes beyond
that (the prefix expanded again for every chunk, padded rows, masked key
blocks) lowers it; it cannot read over 100 %."""

METRIC = {"name": "mla_long_prefill_chunk_mfu", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "model step", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib import roofline_mla_plain as rl

    return rl.chunk_mfu(src) if rl.is_family(src["config"]) else None
