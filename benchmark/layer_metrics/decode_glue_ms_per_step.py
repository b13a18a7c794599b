"""Device milliseconds of one decode step under NO scope of the model:
layout copies on entry to the program, casts, table and position updates,
scan plumbing (a layer's slice of a weight stack that the compiler chose to
copy). What a builder looks at first when a step costs more than its parts.
Over the whole launches of the ``decode``-kind programs in the traced span
(``obs/roofline.py program_kind``), per step as their ``batcher.dispatch``
spans count the steps; the five ``decode_*_ms_per_step`` sum to the decode
program's device time a step (``benchmark/lib/scopes.py``, which gives None
where operations and launches part by more than 2 %). Nothing to read from a
program without the scope vocabulary."""

METRIC = {"name": "decode_glue_ms_per_step", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "device programs", "moves": "gap_p95_ms"}


def read(src):
    from benchmark.lib import scopes

    return scopes.decode_ms_per_step(src, None)
