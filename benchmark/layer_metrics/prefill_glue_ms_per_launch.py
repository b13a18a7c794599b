"""Device milliseconds of one prefill launch under no scope, and under
``mix``, ``head`` and ``embed`` (a prefill's head is one row a prompt): layout
copies, the pool writes of the finishes, the prefix copies, scan plumbing.
Over the whole launches of the ``prefill``-kind programs in the traced span
(``obs/roofline.py program_kind``: chunks, fused admits, finishes, prefix
copies), divided by the launches that run the model (a chunk, a fused admit);
the three ``prefill_*_ms_per_launch`` sum to what a prompt's chunk costs the
device, its share of the finishes included (``benchmark/lib/scopes.py``).
Nothing to read from a program without the scope vocabulary, or where the
span holds no whole prefill launch."""

METRIC = {"name": "prefill_glue_ms_per_launch", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "device programs", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib import scopes

    return scopes.prefill_ms_per_launch(src, None)
