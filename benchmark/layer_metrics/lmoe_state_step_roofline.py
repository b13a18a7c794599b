"""The state-space decode kernel's share of its roofline at several groups
(``ssm_state_step``, one call a Mamba-2 layer a step): the bytes a call must
move (the float32 state of one layer of the slots the step listed, once in and
once out: ``benchmark/lib/roofline_ssm_latent_moe.py``, from the program's own
``state_slots_moved`` / ``state_steps`` of the bursts read back inside the
TRACED SPAN) over the published bandwidth, against the mean device seconds of
a call in the trace. Bandwidth-bound: five operations a state element against
eight bytes."""

METRIC = {"name": "lmoe_state_step_roofline", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib import roofline_ssm_latent_moe as rl

    if not rl.is_family(src["config"]):
        return None
    ds, c = rl.kernel_durations_ns(src, rl.STATE_KERNEL), rl.span_bursts(src)
    if not ds or not c:
        return None
    call_s = sum(ds) / len(ds) / 1e9
    moved = rl.step_means(c)[0]
    return 100.0 * rl.state_step_call_bytes(src["config"], moved) / rl.bandwidth(src) / call_s
