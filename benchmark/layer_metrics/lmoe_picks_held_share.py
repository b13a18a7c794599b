"""Per cent of the (row, pick) pairs the live rows routed in the window's
decode steps whose expert is held on this chip: ``moe_picks_held`` /
``moe_picks`` of the ``batcher.readback`` spans (``BatcherStats.
record_picks``). A chip that holds a quarter of a layer's experts sees ~25
under a balanced live router."""

METRIC = {"name": "lmoe_picks_held_share", "unit": "%", "better": "higher",
          "source": "program_counter", "layer": "model step", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib import roofline_ssm_latent_moe as rl

    c = rl.window_bursts(src) if rl.is_family(src["config"]) else None
    share = rl.held_share(c) if c else None
    return None if share is None else 100.0 * share
