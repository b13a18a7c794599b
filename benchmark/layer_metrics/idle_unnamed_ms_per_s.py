"""Device idle that no span of the program explains: in the traced span,
the gaps between device operations that lie under none of the program's
host spans (``batcher.*``, ``worker.*`` on the host plane, same clock), in
milliseconds per traced second, averaged over the chips. With no spans in
the program it is all of the idle time."""

METRIC = {"name": "idle_unnamed_ms_per_s", "unit": "ms/s", "better": "lower",
          "source": "device_trace", "layer": "device", "moves": "out_tok_s"}


def read(src):
    from benchmark.lib import reduce_trace
    from benchmark.lib.spans import SPAN_PREFIXES, device_ops, overlap, planes

    loaded = planes(src)
    devs = [l for n, l in (loaded or {}).items()
            if reduce_trace.is_device_plane(n) and device_ops(l)]
    if not devs:
        return None
    named = reduce_trace._union([
        (s, s + d) for pname, lines in loaded.items() if pname.startswith("/host:")
        for evs in lines.values() for name, s, d in evs
        if d > 0 and name.startswith(SPAN_PREFIXES)])
    unnamed_ns = traced_ns = 0
    for lines in devs:
        busy = reduce_trace._union([(s, s + d) for _, s, d in device_ops(lines)])
        lo, hi = busy[0][0], busy[-1][1]
        traced_ns += hi - lo
        for (_, gs), (ge, _) in zip(busy, busy[1:]):
            unnamed_ns += (ge - gs) - overlap(named, gs, ge)
    return (unnamed_ns / 1e6) / (traced_ns / 1e9) if traced_ns > 0 else None
