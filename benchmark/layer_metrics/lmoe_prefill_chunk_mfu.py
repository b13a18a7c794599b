"""The chunk launches' share of the chip's bf16 peak in the state-space family
with layers of latent experts: the LEAST operations the traced span's launches
need (projections of their real tokens, the recurrence a token at a time,
causal attention over the pairs their rows really have, router, latent pair,
shared expert and the (token, pick) pairs held here, one head row a prompt row:
``benchmark/lib/roofline_ssm_latent_moe.py chunk_min_flops``, from the
``tokens``, ``rows`` and ``pairs`` of the launches' own ``batcher.admit``
records and the window's held share of the picks) over their device seconds
and the published peak. What the program computes beyond that (the chunked
scan's [Q, Q] products in float32, a tile computed whole for every expert with
a row in it, padded rows) lowers it; it cannot read over 100 %."""

METRIC = {"name": "lmoe_prefill_chunk_mfu", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "model step", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib import roofline_ssm_latent_moe as rl

    return rl.chunk_mfu(src) if rl.is_family(src["config"]) else None
