"""Readers of what the program and JAX expose: compile events, device
memory. Copied from chip_smoke.py (:130, :153)."""

from __future__ import annotations

import time


class CompileClock:
    """Every program XLA built or fetched from the persistent cache, with
    when and for how long (``jax.monitoring``). The event fires around
    ``compile_or_get_cached``, so a cache hit counts too: what is counted is
    "a new program entered this process", which is what must not happen
    inside the measured window."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    REQ = "/jax/compilation_cache/compile_requests_use_cache"

    def __init__(self) -> None:
        from jax import monitoring

        self.events: list[tuple[float, str, float]] = []  # (when, program, seconds)
        self.hits = 0
        self.requests = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, seconds: float, **kw) -> None:
        if event == self.EVENT:
            self.events.append((time.perf_counter(), str(kw.get("fun_name", "?")), seconds))

    def _on_event(self, event: str, **kw) -> None:
        if event == self.HIT:
            self.hits += 1
        elif event == self.REQ:
            self.requests += 1

    def between(self, t0: float, t1: float) -> list[tuple[str, float]]:
        return [(name, s) for t, name, s in self.events if t0 <= t < t1]

    def summary(self, floor_s: float = 1.0) -> dict:
        by: dict[str, float] = {}
        for _, name, s in self.events:
            by[name] = by.get(name, 0.0) + s
        return {
            "programs": len(self.events),
            "seconds": sum(by.values()),
            "cache_hits": self.hits,
            "cache_misses": self.requests - self.hits,
            "over_1s": {k: round(v, 2) for k, v in sorted(by.items(), key=lambda kv: -kv[1])
                        if v >= floor_s},
        }


def memory_by_device() -> list[dict]:
    import jax

    out = []
    for dev in jax.local_devices():
        ms = dev.memory_stats() or {}
        out.append({"id": dev.id, "bytes_in_use": ms.get("bytes_in_use"),
                    "peak_bytes_in_use": ms.get("peak_bytes_in_use"),
                    "bytes_limit": ms.get("bytes_limit")})
    return out
