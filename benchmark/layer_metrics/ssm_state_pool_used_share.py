"""Mean share of the per-slot recurrent-state pool's slots that held a
request, sampled each second of the window (``StatePool.stats()``, beside the
block pool's counts in the batcher's ``pool_stats()``)."""

METRIC = {"name": "ssm_state_pool_used_share", "unit": "%", "better": "higher",
          "source": "program_counter", "layer": "state pool", "moves": "out_tok_s"}


def read(src):
    shares = [100.0 * s["pool"]["state"]["slots_live"] / s["pool"]["state"]["slots_total"]
              for s in src["samples"]
              if s.get("pool") and s["pool"].get("state") and s["pool"]["state"]["slots_total"]]
    return sum(shares) / len(shares) if shares else None
