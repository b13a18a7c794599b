"""Test configuration.

JAX runs on the CPU backend with 8 virtual devices so TP/EP/DP sharding logic
is exercised multi-"device" on one host (SURVEY.md §4.3) — must be set before
jax is first imported anywhere in the test process.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests never touch an accelerator
# persistent compile cache: CPU-backend jit of the scan'd models dominates
# suite runtime otherwise. Where JAX_COMPILATION_CACHE_DIR places it, that
# is honoured; otherwise the program's own fixed in-checkout path
# (config.DEFAULT_COMPILE_CACHE_DIR) — never a temporary name.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# a plugin may have imported jax before this file ran, when env vars alone
# are too late — set the same values through the config API as well (before
# first backend use)
import jax

from nats_llm_studio_tpu.config import DEFAULT_COMPILE_CACHE_DIR

jax.config.update("jax_platforms", "cpu")
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 1)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

import asyncio
import functools

import pytest


def async_test(fn=None, *, timeout: float = 60.0):
    """Run an async test via asyncio.run (no pytest-asyncio in this image);
    ``@async_test(timeout=...)`` for a test that builds more programs than
    60 s of a loaded host with an empty compile cache allow."""

    def wrap(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            asyncio.run(asyncio.wait_for(fn(*args, **kwargs), timeout=timeout))

        return wrapper

    return wrap if fn is None else wrap(fn)


def hold_decodes_until_queued(b, after: int = 3, timeout_s: float = 30.0) -> None:
    """From its ``after``-th decode dispatch on, ``b``'s owner thread waits,
    once, until another request lies in its inbox. A test that needs a second
    request to arrive WHILE the first still decodes gets the event itself:
    with the owner running free, a short first request can finish before the
    test's event loop has submitted the second one (a loaded host), and then
    nothing is preempted, suspended or shed."""
    import time

    seen = {"dispatches": 0, "released": False}

    def gated(fn):
        def run(*args, **kwargs):
            seen["dispatches"] += 1
            if not seen["released"] and seen["dispatches"] > after:
                end = time.monotonic() + timeout_s
                while b._inbox.qsize() == 0 and time.monotonic() < end:
                    time.sleep(0.001)
                seen["released"] = True
            return fn(*args, **kwargs)
        return run

    for name, fn in list(vars(b).items()):
        if name.startswith("_decode") and callable(fn):
            setattr(b, name, gated(fn))


@pytest.fixture
def tmp_models_dir(tmp_path):
    d = tmp_path / "models"
    d.mkdir()
    return d
