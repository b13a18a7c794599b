"""Process-wide XLA compile-cache hit/miss counters.

JAX's persistent compilation cache (placed by ``JAX_COMPILATION_CACHE_DIR``
or, without it, by config.configure_jax) reports hits and misses only through
``jax.monitoring`` events — invisible to operators unless something
listens. This module turns them into two monotonic counters the worker
exposes as ``lmstudio_compile_cache_{hits,misses}_total``, which is how
you tell "the restart re-jitted the whole grid from the cache in
seconds" apart from "the cache was cold/evicted and every program paid a
full XLA compile".

The build ledger beside them answers "where did start-up go": seconds this
process spent building programs, by kind (JAX's own duration events) and by
program (``fun_name``), shown as ``lmstudio_program_build_seconds_total{kind}``:

    trace       tracing a function to a jaxpr
    lower       jaxpr -> MLIR module
    compile     ``compile_or_get_cached``: the XLA compile on a cache miss,
                the load from the persistent cache on a hit
    cache_load  the cache reads inside ``compile`` (a part of it, not added
                to the total)

Import-light like the rest of obs/: jax is imported inside the installer
only, and installation is idempotent (the worker calls it at startup;
tests may call it again freely).
"""

from __future__ import annotations

import collections
import threading
import time

_lock = threading.Lock()
_counts = {"hits": 0, "misses": 0, "requests": 0}
_installed = False

# jax.monitoring event suffixes → counter keys (the events are
# /jax/compilation_cache/cache_{hits,misses}; ``misses`` fires when an entry
# is WRITTEN, ``requests`` for every program that asked the cache, so
# requests - hits is what was compiled whether or not it was kept)
_EVENT_KEYS = {"cache_hits": "hits", "cache_misses": "misses",
               "compile_requests_use_cache": "requests"}

# jax.monitoring duration events → build-ledger kinds
BUILD_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
_TOTAL_KINDS = ("trace", "lower", "compile")  # cache_load lies inside compile
_seconds = dict.fromkeys(BUILD_KINDS.values(), 0.0)
_by_program: dict[str, dict[str, float]] = {}
# the newest entries (perf_counter, key, program, amount) of counts and
# seconds alike, so that a reader can ask for the ledger as it stood at an
# earlier instant: totals now minus what came after it
_recent: collections.deque = collections.deque(maxlen=4096)


def _on_event(event: str, **kwargs) -> None:
    key = _EVENT_KEYS.get(event.rsplit("/", 1)[-1])
    if key is not None:
        with _lock:
            _counts[key] += 1
            _recent.append((time.perf_counter(), key, "", 1))


def _on_duration(event: str, seconds: float, **kwargs) -> None:
    kind = BUILD_KINDS.get(event)
    if kind is None:
        return
    program = str(kwargs.get("fun_name", ""))
    if program.startswith("jit(") and program.endswith(")"):
        program = program[4:-1]  # lower/compile say jit(f) where trace says f
    with _lock:
        _seconds[kind] += seconds
        if program:
            by = _by_program.setdefault(program, {})
            by[kind] = by.get(kind, 0.0) + seconds
        _recent.append((time.perf_counter(), kind, program, seconds))


def install_compile_cache_listener() -> bool:
    """Register the jax.monitoring listener once per process. Returns True
    once the listener is installed. Safe to call repeatedly."""
    global _installed
    with _lock:
        if _installed:
            return True
    from jax import monitoring

    with _lock:
        if _installed:  # lost a race to another caller
            return True
        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _installed = True
    return True


def compile_cache_counts() -> dict[str, int]:
    """Snapshot of {hits, misses, requests} since install (zeros before)."""
    with _lock:
        return dict(_counts)


def build_ledger(until: float | None = None, top: int = 10) -> dict:
    """The ledger now, or as it stood at ``until`` (``time.perf_counter``):
    ``seconds`` by kind, their ``total_s`` (trace + lower + compile), the
    cache ``hits`` / ``misses`` / ``requests``, and the ``top`` costliest
    programs as ``[name, seconds, {kind: seconds}]``."""
    with _lock:
        seconds = dict(_seconds)
        counts = dict(_counts)
        programs = {k: dict(v) for k, v in _by_program.items()}
        later = [e for e in _recent if e[0] > until] if until is not None else []
    for _, key, program, amount in later:
        if key in counts:
            counts[key] -= amount
        else:
            seconds[key] -= amount
            if program:
                programs[program][key] -= amount
    ranked = sorted(
        ([name, sum(by.get(k, 0.0) for k in _TOTAL_KINDS), by]
         for name, by in programs.items()), key=lambda row: -row[1])
    return {"seconds": seconds, "total_s": sum(seconds[k] for k in _TOTAL_KINDS),
            **counts, "programs": ranked[:top]}
