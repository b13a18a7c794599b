"""The decode step's share of its roofline in the state-space family with
layers of latent experts: the bytes one step must move (every weight outside
the routed experts once with the head, the experts held here that the step's
rows hit, the listed slots' state and convolution tails of every Mamba-2 layer
once in and once out, the live tokens' keys and values in the attention layer:
``benchmark/lib/roofline_ssm_latent_moe.py``, all from the run's own counters
of the bursts read back inside the TRACED SPAN) over the published bandwidth,
against the device seconds of one step of the burst decode program (launches
wholly inside the traced span)."""

METRIC = {"name": "lmoe_decode_step_roofline", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib import roofline_ssm_latent_moe as rl

    if not rl.is_family(src["config"]):
        return None
    c, kv, step_s = rl.span_bursts(src), rl.live_tokens(src), rl.decode_step_seconds(src)
    if not c or kv is None or not step_s:
        return None
    moved, _, hit = rl.step_means(c)
    need = rl.decode_step_bytes(src["config"], moved, kv, hit)
    return 100.0 * need / rl.bandwidth(src) / step_s
