"""What the program's span ring, build ledger and host plane give the
per-layer readers (``nats_llm_studio_tpu/obs/spans.py``,
``obs/compile_cache.py``). A program that has no ring or ledger yet (a
parent commit) gives ``None``: the reader then returns ``None`` and the
metric is left out of the line. A test hands a recorded fixture in under
``src["spans"]``, ``src["build_ledger"]`` or ``src["planes"]``."""

from __future__ import annotations

import os
from pathlib import Path

from benchmark.lib import reduce_trace

# a run's scratch (the model's header, the store, the trace): a directory a
# process, which ``run.py`` removes at its end
RUN_DIR = Path(__file__).resolve().parent.parent / ".cache" / f"run-{os.getpid()}"
TRACE_DIR = RUN_DIR / "trace"
# host events under these prefixes are the program's own spans
SPAN_PREFIXES = ("batcher.", "worker.")
_planes_cache: dict = {}


def window_records(src, name: str) -> list[tuple] | None:
    """The ring's ``(name, t0, t1, attrs)`` records of one name that overlap
    the window, oldest first."""
    w0, w1 = src["window"]
    if "spans" in src:
        return [r for r in src["spans"] if r[0] == name and r[1] <= w1 and r[2] >= w0]
    try:
        from nats_llm_studio_tpu.obs import spans
    except ImportError:
        return None
    return spans.records(w0, w1, name)


def traced_span(src) -> tuple[float, float]:
    """The traced span on the host's clock: as ``run.py`` started and stopped
    the profiler (``src["span"]``), else as its ``_traced_window`` places it:
    up to 4 s in the middle of the window."""
    if src.get("span") and None not in src["span"]:
        return tuple(src["span"])
    w0, w1 = src["window"]
    span = min(4.0, (w1 - w0) / 3.0)
    t_on = w0 + (w1 - w0 - span) / 2.0
    return t_on, t_on + span


def readback_sums(src, lo: float, hi: float) -> dict:
    """Every counter the ``batcher.readback`` spans that end in [lo, hi)
    carry, summed: what the bursts of the window, or of the traced span,
    counted (rows, steps, experts hit, keys), for the run's ``trace`` line."""
    tot: dict = {}
    for _, _, t1, attrs in window_records(src, "batcher.readback") or []:
        if lo <= t1 < hi:
            for k, v in (attrs or {}).items():
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    tot[k] = tot.get(k, 0) + v
    return tot


def ledger_at_window_start(src) -> dict | None:
    """The program's build ledger as it stood when the window began;
    ``None`` too where its listener saw no program ask the cache."""
    if "build_ledger" in src:
        ledger = src["build_ledger"]
    else:
        try:
            from nats_llm_studio_tpu.obs.compile_cache import build_ledger
        except ImportError:
            return None
        ledger = build_ledger(until=src["window"][0])
    return ledger if ledger and ledger.get("requests") else None


def planes(src) -> dict | None:
    """The traced run's planes (``reduce_trace.load_planes``), loaded once
    for all readers; ``None`` where no trace was written."""
    if "planes" in src:
        return src["planes"]
    try:
        path = reduce_trace.find_xplane(str(TRACE_DIR))
    except FileNotFoundError:
        return None
    key = (path, Path(path).stat().st_mtime_ns)
    if key not in _planes_cache:
        _planes_cache.clear()
        _planes_cache[key] = reduce_trace.load_planes(path)
    return _planes_cache[key]


def device_ops(lines: dict) -> list[tuple]:
    return lines.get(reduce_trace.OPS_LINE) or lines.get(reduce_trace.MODULES_LINE) or []


def overlap(intervals: list[tuple[int, int]], lo: int, hi: int) -> int:
    """Length of ``[lo, hi]`` covered by already-merged ``intervals``."""
    return sum(max(0, min(e, hi) - max(s, lo)) for s, e in intervals)
