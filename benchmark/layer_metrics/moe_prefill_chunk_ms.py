"""Device milliseconds of one launch of the chunked group prefill
(``prefill_chunk_group``: 2 or 4 prompts' chunks of 256 tokens through every
layer): the device seconds of its launches that lie wholly inside the traced
span over their count, as the trace reduction's ``programs`` gives both. In a
routed-expert model the expert layers are the largest part of such a launch,
and the form they take (dense dispatch computes every expert for every row,
the grouped form each row's picks only) is what moves it. Nothing to read
where the span holds no whole launch of that program."""

METRIC = {"name": "moe_prefill_chunk_ms", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "model step", "moves": "out_tok_s"}

PROGRAM = "prefill_chunk_group"


def read(src):
    p = src["trace"].get("programs", {}).get(PROGRAM)
    if not p or not p["launches"]:
        return None
    return 1e3 * p["seconds"] / p["launches"]
