"""Share of the device's busy time that the ``prefill``-kind programs hold, in
per cent: device seconds of their whole launches in the traced span over the
busy seconds of the same stretch (first whole launch to last). The kinds are
the program's own (``obs/roofline.py program_kind``, from its table names),
not a list kept here. What decode does not get: at one chunk a burst it is
the lever on ``out_tok_s`` in every cell (ROADMAP A1)."""

METRIC = {"name": "prefill_device_share", "unit": "%", "better": "lower",
          "source": "device_trace", "layer": "device programs", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib import scopes

    return scopes.prefill_device_share(src)
