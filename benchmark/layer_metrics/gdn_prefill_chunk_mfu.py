"""The chunk launches' share of the chip's bf16 peak in the linear-attention
family: the LEAST operations the traced span's launches need (projections of
their real tokens, the delta rule a token at a time, causal attention over
the pairs their rows really have, router, shared expert and the (token, pick)
pairs held here, one head row a prompt row:
``benchmark/lib/roofline_gdn_moe.py chunk_min_flops``, from the ``tokens``,
``rows`` and ``pairs`` of the launches' own ``batcher.admit`` records and the
window's held share of the picks) over their device seconds and the published
peak. What the program computes beyond that (the chunked form's solve and
[C, C] products in float32, padded rows) lowers it; it cannot read over 100 %."""

METRIC = {"name": "gdn_prefill_chunk_mfu", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "model step", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib import roofline_gdn_moe as rl

    return rl.chunk_mfu(src) if rl.is_family(src["config"]) else None
