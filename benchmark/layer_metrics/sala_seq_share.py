"""Per cent of the decode programs' device time that lies under the scopes
``seq/linear`` and ``seq/sparse`` (both mixers whole: projections, norms,
rotary, the state kernel, the pooled-key write, the selection, the picked
walk, gates, output projections): the device time of the operations whose
``op_name`` lies under them over the time of the decode launches that lie
wholly inside the traced span (``benchmark/lib/scopes.py``). What is left is
the dense SwiGLU, the head and the sampling."""

METRIC = {"name": "sala_seq_share", "unit": "%", "better": "lower",
          "source": "device_trace", "layer": "model step", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib import roofline_sala as rl

    return rl.decode_scope_share(src) if rl.is_family(src["config"]) else None
