"""From a profiler trace (``.xplane.pb``) to numbers, with nothing but JAX:
``jax.profiler.ProfileData``. Kept with the benchmark so that every PR
computes the same number in the same way.

What a TPU trace holds (looked at by hand, PR 23): one plane per chip named
``/device:TPU:<n>`` whose lines include ``XLA Modules`` (one event per run
of a jitted program, named ``jit_<fn>(<fingerprint>)``) and ``XLA Ops`` (one
event per HLO operation inside them, named by its whole HLO line:
``%closed_call.19 = bf16[8,8,16,128]{...} custom-call(...)``; a ``while`` or
``conditional`` is an event too and covers its body's events), and
``/host:CPU`` with one line per host thread (``PjitFunction(<fn>)``,
transfers, allocator waits). All share one clock, in nanoseconds. A Pallas
kernel is a ``custom-call``.

    busy_s      union of the intervals in which an operation ran on the
                device, averaged over the device planes
    window_s    first to last event over all device planes (or the span the
                caller gives)
    device_ops  seconds per operation (``%name opcode``), containers left
                out, the top N; ``by_opcode`` sums them per opcode
    programs    per jitted program: launches and device seconds
    idle_gaps   the longest gaps between device operations, each with the
                host event that overlapped it most
"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
# operations that only wrap others: their time is their children's
CONTAINERS = ("while", "conditional", "call")
_OPCODE = re.compile(r"[\}\)] ([a-z][a-z0-9_\-]*)\(")
MODULES_LINE = "XLA Modules"
TOP_N = 10


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_planes(path: str) -> dict:
    """``{plane name: {line name: [(name, start_ns, duration_ns), ...]}}``:
    the plain form the reduction (and the recorded fixture) works on."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: dict = {}
    for plane in data.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for ev in line.events:
                evs.append((ev.name, int(ev.start_ns), int(ev.duration_ns)))
    return out


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:") and "SparseCore" not in name


def program_name(module_event: str) -> str:
    """``jit_decode_pos_pallas(1234567890)`` -> ``decode_pos_pallas``."""
    name = re.sub(r"\(\d+\)$", "", module_event)
    return name[4:] if name.startswith("jit_") else name


def op_label(event_name: str) -> tuple[str, str]:
    """An ``XLA Ops`` event's HLO line -> (``%name opcode``, opcode); a
    name that is already short passes through."""
    head, sep, rest = event_name.partition(" = ")
    if not sep:
        parts = event_name.split(" ")
        return event_name, parts[1] if len(parts) > 1 else ""
    m = _OPCODE.search(rest)
    opcode = m.group(1) if m else ""
    return f"{head} {opcode}".strip(), opcode


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _host_events(planes: dict) -> list[tuple[int, int, str]]:
    out = []
    for pname, lines in planes.items():
        if not pname.startswith("/host:"):
            continue
        for lname, evs in lines.items():
            for name, s, d in evs:
                if d > 0:
                    out.append((s, s + d, f"{lname.split('/')[0]}:{name}"))
    return out


def _attribute(gap: tuple[int, int], host: list[tuple[int, int, str]]) -> str:
    """The innermost host span over the gap: the shortest event that
    overlaps at least half of it."""
    gs, ge = gap
    best, best_len = "unattributed", None
    for s, e, name in host:
        if 2 * (min(e, ge) - max(s, gs)) >= ge - gs and (best_len is None or e - s < best_len):
            best, best_len = name, e - s
    return best


def reduce(planes: dict, span_ns: tuple[int, int] | None = None) -> dict:
    devs = {n: l for n, l in planes.items() if is_device_plane(n)}
    if not devs:
        return {"device_planes": 0}
    host = _host_events(planes)
    busy_ns, ops_s, opcode_s, programs, gaps = [], {}, {}, {}, []
    lo = min(s for l in devs.values() for evs in l.values() for _, s, _ in evs)
    hi = max(s + d for l in devs.values() for evs in l.values() for _, s, d in evs)
    if span_ns is not None:
        lo, hi = span_ns
    for lines in devs.values():
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        ivals = [(max(s, lo), min(s + d, hi)) for _, s, d in ops if s + d > lo and s < hi]
        merged = _union(ivals)
        busy_ns.append(sum(e - s for s, e in merged))
        for name, s, d in ops:
            if s + d > lo and s < hi:
                label, opcode = op_label(name)
                if opcode in CONTAINERS:
                    continue
                ops_s[label] = ops_s.get(label, 0.0) + d / 1e9
                opcode_s[opcode] = opcode_s.get(opcode, 0.0) + d / 1e9
        for name, s, d in lines.get(MODULES_LINE, []):
            if s >= lo and s + d <= hi:
                p = programs.setdefault(program_name(name), {"launches": 0, "seconds": 0.0})
                p["launches"] += 1
                p["seconds"] += d / 1e9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n = len(devs)
    # programs and ops are summed over the chips: average them like busy_s
    for p in programs.values():
        p["seconds"] /= n
        p["launches"] /= n
    gaps.sort(key=lambda g: g[0] - g[1])
    by_cause: dict[str, float] = {}
    for g in gaps[:200]:
        cause = _attribute(g, host)
        by_cause[cause] = by_cause.get(cause, 0.0) + (g[1] - g[0]) / 1e9 / n
    return {
        "device_planes": n,
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_ns) / n / 1e9,
        "device_ops": sorted(([k, v / n] for k, v in ops_s.items()),
                             key=lambda kv: -kv[1])[:TOP_N],
        "by_opcode": {k: v / n for k, v in opcode_s.items()},
        "programs": programs,
        "idle_gaps": sorted(([k, v] for k, v in by_cause.items()),
                            key=lambda kv: -kv[1])[:TOP_N],
        "longest_gap_s": (gaps[0][1] - gaps[0][0]) / 1e9 if gaps else 0.0,
    }
