"""Device milliseconds of the paged decode-attention Pallas kernel per
decode step: the trace's ``custom-call`` operations that carry the kernel's
fixed name (``ops/paged_attention.py``: ``name="paged_decode_attention"``),
summed over the traced span and averaged over the chips, over the decode
steps launched in it (launches of the decode programs x steps per launch).
A program whose kernel has no fixed name gives nothing to read."""

METRIC = {"name": "attn_kernel_ms_per_step", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "kernels", "moves": "gap_p95_ms"}

KERNEL = "paged_decode_attention"


def read(src):
    from benchmark.lib import reduce_trace
    from benchmark.lib.spans import device_ops, planes

    progs = [v for k, v in src["trace"].get("programs", {}).items()
             if "decode" in k and "ext" not in k]
    steps = sum(p["launches"] for p in progs) * (src["engine"].get("decode_burst") or 0)
    devs = [l for n, l in (planes(src) or {}).items() if reduce_trace.is_device_plane(n)]
    if not devs or steps <= 0:
        return None
    kernel_ns = 0
    for lines in devs:
        for name, _, d in device_ops(lines):
            label, opcode = reduce_trace.op_label(name)
            if opcode == "custom-call" and KERNEL in label:
                kernel_ns += d
    return (kernel_ns / len(devs) / 1e6) / steps if kernel_ns else None
