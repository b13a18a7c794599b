"""Device milliseconds of one decode step under the scopes ``head`` and
``embed``: final norm, ``lm_head``, sampling and the token write, and the
embedding of the step's one token a slot.
Over the whole launches of the ``decode``-kind programs in the traced span
(``obs/roofline.py program_kind``), per step as their ``batcher.dispatch``
spans count the steps; the five ``decode_*_ms_per_step`` sum to the decode
program's device time a step (``benchmark/lib/scopes.py``, which gives None
where operations and launches part by more than 2 %). Nothing to read from a
program without the scope vocabulary."""

METRIC = {"name": "decode_head_ms_per_step", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "model step", "moves": "gap_p95_ms"}


def read(src):
    from benchmark.lib import scopes

    return scopes.decode_ms_per_step(src, "head")
