"""The decode step's share of its roofline in a model of window layers beside
full layers with routed experts: the bytes one step must read (every weight
outside the routed experts with the head, the experts the live rows HIT by
the program's counter, the live rows' whole contexts in the 2 full layers and
min(context, 512) keys in the 3 window layers:
``benchmark/lib/roofline_swa_moe.py``) over the published bandwidth, against
the device seconds of one step of the burst decode program (launches wholly
inside the traced span, counted as ``moe_decode_step_roofline`` counts them).
Rows, contexts and experts hit are the traced span's own bursts', not the
window's mean. The ring kernel reads every slot's ring, live or not, and the
step spends time outside any read (launches, the sampler's sort), so it reads
under its bound by design."""

METRIC = {"name": "swa_decode_step_roofline", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib import roofline_swa_moe as rl

    if not rl.is_family(src["config"]):
        return None
    c, step_s = rl.span_counters(src), rl.decode_step_seconds(src)
    if not c or not c["expert_steps"] or not step_s:
        return None
    steps = c["win_steps"]
    need = rl.decode_step_bytes(
        src["config"], c["experts_hit"] / c["expert_steps"], c["full_tokens"] / steps,
        c["win_tokens"] / steps, c["expert_rows"] / c["expert_steps"])
    return 100.0 * need / rl.bandwidth(src) / step_s
