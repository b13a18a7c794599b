"""The routed-expert kernel's share of its roofline in a decode step of the
latent-expert layers (``moe_hit_experts`` or ``moe_grouped_experts``, one call
an expert layer a step, whichever form ``models/experts.py expert_path`` gives
the step): the bytes a call must move (the two matrices of every expert held
here that the step's live rows hit: ``experts_hit`` / ``expert_steps`` of the
bursts read back inside the TRACED SPAN, times
``benchmark/lib/roofline_ssm_latent_moe.py expert_bytes``) over the published
bandwidth, against the mean device seconds of a call inside the burst decode
program's launches."""

METRIC = {"name": "lmoe_experts_roofline", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib import roofline_ssm_latent_moe as rl

    if not rl.is_family(src["config"]):
        return None
    call_s, c = rl.expert_call_seconds(src), rl.span_bursts(src)
    if not call_s or not c:
        return None
    need = rl.step_means(c)[2] * rl.expert_bytes(src["config"])
    return 100.0 * need / rl.bandwidth(src) / call_s
