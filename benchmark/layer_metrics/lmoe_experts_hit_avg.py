"""Experts HELD HERE that the live rows of a decode step hit, averaged over
the window's steps and expert layers, in the state-space family with layers of
latent experts: what a step streams of each layer's 128 held experts, and the
number that says whether two seeds did the same work. ``experts_hit`` /
``expert_steps`` of the ``batcher.readback`` spans of the bursts read back
inside the window (``BatcherStats.record_moe``). Under the cell's LIVE router
it sits near its ceiling, and higher is the cell doing what it is there for."""

METRIC = {"name": "lmoe_experts_hit_avg", "unit": "experts/step", "better": "higher",
          "source": "program_counter", "layer": "model step", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib import roofline_ssm_latent_moe as rl

    c = rl.window_bursts(src) if rl.is_family(src["config"]) else None
    return rl.step_means(c)[2] if c else None
