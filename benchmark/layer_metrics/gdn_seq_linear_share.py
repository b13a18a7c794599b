"""Per cent of the decode programs' device time that lies under the scope
``seq/linear`` (the linear-attention layers' in-projections, convolution,
state kernel, gated norm and output projection): the device time of the
operations whose ``op_name`` lies under it over the time of the decode
launches that lie wholly inside the traced span (``benchmark/lib/scopes.py``).
Nothing to read where the program opens no such scope."""

METRIC = {"name": "gdn_seq_linear_share", "unit": "%", "better": "lower",
          "source": "device_trace", "layer": "model step", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib import roofline_gdn_moe as rl

    return rl.decode_scope_share(src) if rl.is_family(src["config"]) else None
