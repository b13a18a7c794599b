"""The decode step's share of its roofline in a model of state-space layers
beside attention layers: the bytes one step must move (every weight once with
the head, the LIVE rows' recurrent state and convolution tails once in and
once out, the live tokens' keys and values in the attention layers:
``benchmark/lib/roofline_ssm_hybrid.py``) over the published bandwidth,
against the device seconds of one step of the burst decode program (launches
wholly inside the traced span, counted as ``moe_decode_step_roofline`` counts
them). Live rows are the program's own count (``state_rows`` / ``state_steps``)
of the bursts read back inside the TRACED SPAN, whose seconds they are priced
against (``span_live_rows``): since PR 36 the state kernel moves the slots
that hold a request and no others, so the step's time follows the rows."""

METRIC = {"name": "ssm_decode_step_roofline", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib import roofline_ssm_hybrid as rl

    if "layer_types" not in src["config"]:
        return None
    rows, kv, step_s = rl.span_live_rows(src), rl.live_tokens(src), rl.decode_step_seconds(src)
    if rows is None or kv is None or not step_s:
        return None
    need = rl.decode_step_bytes(src["config"], rows, kv)
    return 100.0 * need / rl.bandwidth(src) / step_s
