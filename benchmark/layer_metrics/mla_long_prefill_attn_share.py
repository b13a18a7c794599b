"""Per cent of the chunk launches' device time under the scope ``seq/mla``
(the projections, the row-cache write and the blocked expanded attention
over the live prefix): how much of a long prompt's prefill is latent
attention, where in ``xing29b.answer_closed``'s contexts of 3.5k it is the
smaller part. Over the launches of ``prefill_chunk_group`` and ``prefill1``
wholly inside the traced span (``benchmark/lib/scopes.py``'s table). Nothing
to read from a program without the scope vocabulary."""

METRIC = {"name": "mla_long_prefill_attn_share", "unit": "%", "better": "lower",
          "source": "device_trace", "layer": "model step", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib import roofline_mla_plain as rl

    return rl.chunk_scope_share(src) if rl.is_family(src["config"]) else None
