"""Device milliseconds a decode step spends picking blocks in the block-sparse
layers (all of them together): the operations whose ``op_name`` lies under the
scope ``seq/sparse/select`` (the query's scores against the slot's pooled
keys, the group's softmax, the blocks' maxima, the forced blocks, the top-k,
the sort and the picked table) of the decode launches that lie wholly inside
the traced span, over their steps (``benchmark/lib/scopes.py``). Nothing to
read where the program opens no such scope."""

METRIC = {"name": "sala_select_ms_per_step", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "model step", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib import roofline_sala as rl

    return rl.select_ms_per_step(src) if rl.is_family(src["config"]) else None
