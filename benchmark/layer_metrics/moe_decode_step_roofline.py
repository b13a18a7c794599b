"""The decode step's share of its roofline in a routed-expert model: the
bytes one step must read (every weight outside the routed experts, the
experts the live rows HIT by the program's counter, the live latents:
``benchmark/lib/roofline_mla_moe.py``) over the published bandwidth, against
the device seconds of one step of the burst decode program (launches wholly
inside the traced span). A dense dispatch reads all 64 experts of a layer
where ~20 are hit, so it reads low by design: the room a grouped expert
matmul has."""

METRIC = {"name": "moe_decode_step_roofline", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib import roofline_mla_moe as rl

    c, live, step_s = rl.window_moe_counters(src), rl.live_tokens(src), rl.decode_step_seconds(src)
    if not c or live is None or not step_s:
        return None
    hit = c["experts_hit"] / c["expert_steps"]
    rows = c["expert_rows"] / c["expert_steps"]
    need = rl.decode_step_bytes(src["config"], hit, live, rows)
    return 100.0 * need / rl.bandwidth(src) / step_s
