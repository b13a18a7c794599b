"""The picked walk's share of its roofline (``paged_decode_attention_picked``,
one call a block-sparse layer a step): the bytes a call must move (the keys and
values, both kv heads, of the keys its rows' picks hold:
``benchmark/lib/roofline_sala.py``, from the program's own
``sparse_tokens_picked`` / ``state_steps`` of the bursts read back inside the
TRACED SPAN, a layer's share of them) over the published bandwidth, against
the mean device seconds of a call in the trace."""

METRIC = {"name": "sala_picked_walk_roofline", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib import roofline_sala as rl

    if not rl.is_family(src["config"]):
        return None
    ds, c = rl.kernel_durations_ns(src, rl.WALK_KERNEL), rl.span_bursts(src)
    if not ds or not c:
        return None
    call_s = sum(ds) / len(ds) / 1e9
    picked = rl.step_means(c)[1] / rl.kinds(src["config"])[1]
    return 100.0 * rl.picked_walk_call_bytes(src["config"], picked) / rl.bandwidth(src) / call_s
