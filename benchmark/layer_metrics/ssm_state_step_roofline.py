"""The state-space decode kernel's share of its roofline (``ssm_state_step``,
one call a state-space layer a step): the bytes a call must move (the LIVE
rows' float32 state of one layer once in and once out,
``benchmark/lib/roofline_ssm_hybrid.py``) over the published bandwidth,
against the mean device seconds of a call in the trace. Bandwidth-bound: four
operations a state element against eight bytes. Since PR 36 the kernel moves
the slots that hold a request and no others, so its time follows the live
rows: rows and seconds are both the TRACED SPAN's (``span_live_rows``: the
``batcher.readback`` spans that end inside it), and the share's ceiling is
the kernel's own ~80 % of bandwidth on what it moves, whatever the rows."""

METRIC = {"name": "ssm_state_step_roofline", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib import roofline_ssm_hybrid as rl

    if "layer_types" not in src["config"]:
        return None
    ds, rows = rl.kernel_durations_ns(src, rl.STATE_KERNEL), rl.span_live_rows(src)
    if not ds or not rows:
        return None
    call_s = sum(ds) / len(ds) / 1e9
    return 100.0 * rl.state_step_call_bytes(src["config"], rows) / rl.bandwidth(src) / call_s
