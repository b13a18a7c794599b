"""Share of the device's busy time spent in Pallas kernels: the trace's
``custom-call`` operations (the paged-attention decode kernel, the flash
prefill kernels) over the union of all device operations."""

METRIC = {"name": "pallas_share_of_busy", "unit": "%", "better": "lower",
          "source": "device_trace", "layer": "kernels", "moves": "gap_p95_ms"}


def read(src):
    trace = src["trace"]
    if not trace.get("busy_s") or "custom-call" not in trace.get("by_opcode", {}):
        return None
    return 100.0 * trace["by_opcode"]["custom-call"] / trace["busy_s"]
