"""Mean share of the per-slot recurrent-state pool's slots that held a
request, sampled each second of the window, in the state-space family with
layers of latent experts (``StatePool.stats()``, beside the block pool's counts
in the batcher's ``pool_stats()``)."""

METRIC = {"name": "lmoe_state_pool_used_share", "unit": "%", "better": "higher",
          "source": "program_counter", "layer": "state pool", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib import roofline_ssm_latent_moe as rl

    if not rl.is_family(src["config"]):
        return None
    shares = [100.0 * s["pool"]["state"]["slots_live"] / s["pool"]["state"]["slots_total"]
              for s in src["samples"]
              if s.get("pool") and s["pool"].get("state") and s["pool"]["state"]["slots_total"]]
    return sum(shares) / len(shares) if shares else None
