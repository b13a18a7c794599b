"""Process-wide XLA compile-cache hit/miss counters.

JAX's persistent compilation cache (placed by ``JAX_COMPILATION_CACHE_DIR``
or, without it, by config.configure_jax) reports hits and misses only through
``jax.monitoring`` events — invisible to operators unless something
listens. This module turns them into two monotonic counters the worker
exposes as ``lmstudio_compile_cache_{hits,misses}_total``, which is how
you tell "the restart re-jitted the whole grid from the cache in
seconds" apart from "the cache was cold/evicted and every program paid a
full XLA compile".

Import-light like the rest of obs/: jax is imported inside the installer
only, and installation is idempotent (the worker calls it at startup;
tests may call it again freely).
"""

from __future__ import annotations

import threading

_lock = threading.Lock()
_counts = {"hits": 0, "misses": 0}
_installed = False

# jax.monitoring event suffixes → counter keys (the events are
# /jax/compilation_cache/cache_{hits,misses})
_EVENT_KEYS = {"cache_hits": "hits", "cache_misses": "misses"}


def _on_event(event: str, **kwargs) -> None:
    key = _EVENT_KEYS.get(event.rsplit("/", 1)[-1])
    if key is not None:
        with _lock:
            _counts[key] += 1


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
        _installed = True
    return True


def compile_cache_counts() -> dict[str, int]:
    """Snapshot of {hits, misses} since install (zeros before install)."""
    with _lock:
        return dict(_counts)
