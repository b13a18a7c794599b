"""The decode program's share of its roofline: the least time the chip
could take for one decode step (``benchmark/lib/roofline.py``: the bytes it
must read, from shapes, over the published bandwidth; or its operations over
the bf16 peak, whichever is larger) over the device time one step took in
the trace (device seconds of the decode programs / launches / steps per
launch). Live KV tokens are the pool's live blocks x block size, averaged
over the window's samples; rows are the tokens delivered per step."""

METRIC = {"name": "decode_step_roofline", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels", "moves": "gap_p95_ms"}


def read(src):
    from benchmark.lib.roofline import decode_step_bound_s

    progs = {k: v for k, v in src["trace"].get("programs", {}).items()
             if "decode" in k and "ext" not in k and v["launches"] > 0}
    burst = src["engine"].get("decode_burst")
    if not progs or not burst:
        return None
    launches = sum(p["launches"] for p in progs.values())
    step_s = sum(p["seconds"] for p in progs.values()) / launches / burst
    pools = [s["pool"] for s in src["samples"] if s.get("pool")]
    live = (sum(p["blocks_live"] * p["block_tokens"] for p in pools) / len(pools)) if pools else 0.0
    a, b = src["stats_before"], src["stats_after"]
    steps = b["steps"] - a["steps"]
    rows = (b["tokens"] - a["tokens"]) / steps if steps > 0 else 1.0
    env = src["env"]
    bound = decode_step_bound_s(
        src["config"], src["device"]["kind"], env.get("WQUANT", "none"),
        1.0 if env.get("TPU_KV_QUANT") == "int8" else 2.0, live, rows,
        chips=src["device"]["count"])
    return 100.0 * bound["bound_s"] / step_s
