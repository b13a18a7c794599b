"""Host spans on the device trace's clock, and the one ring that keeps them.

A span does two things and nothing else:

* while a ``jax.profiler`` session is on it is a ``TraceAnnotation`` under
  its fixed name, so it lands on the host plane of the ``.xplane.pb`` on the
  trace's own clock, next to the device operations it waited for or fed;
* it appends ``(name, t0, t1, attrs)`` on ``time.perf_counter`` to one
  bounded ring, which readers filter by a window (``records``).

Spans fire per decode burst, per admit and per published chunk — never per
token and never per layer. The names are a fixed set (``SPAN_NAMES``): the
trace reduction and the benchmark's readers key on them.

Import-light like the rest of obs/: ``jax.profiler`` is handed in by the
module that already imports JAX (``serve/batcher.py`` calls
``use_annotation(jax.profiler.TraceAnnotation)`` at import). Until then, and
in a process that never loads a batcher, a span only records.
"""

from __future__ import annotations

import collections
import time

# every span the program opens, in the owner loop's order, then the reply path
SPAN_NAMES = (
    "batcher.intake",      # the _inbox.get loop: waiting for work (idle by design)
    "batcher.tick",        # brownout, recorder, tier demote, deadline sweeps, resume, DRR
    "batcher.admit",       # host preparation + enqueue of a prefill/admit program
    "batcher.dispatch",    # host preparation + enqueue of a decode burst
    "batcher.readback",    # np.asarray(...) of a burst: owner thread blocked on the device
    "batcher.deliver",     # the row loop after a readback, through _deliver and req.emit
    "worker.publish",      # serialising a chunk + await nc.publish on the loop thread
)

# ~200 records a second at 8 slots and a 50 ms burst (5 owner-thread spans a
# burst, a publish a stream): ten minutes. A traced benchmark run reads its
# window's records ~3 minutes after the window (the profiler writes its file
# first, under load): 16384 had rolled past it by then (PERF.md, PR 30)
RING_SIZE = 131072

# deque.append / iteration snapshots are atomic under the GIL; the owner
# thread and the event-loop thread both append
_ring: collections.deque = collections.deque(maxlen=RING_SIZE)
_annotation = None  # jax.profiler.TraceAnnotation once a JAX importer hands it in


def use_annotation(factory) -> None:
    """Hand in ``jax.profiler.TraceAnnotation`` (idempotent)."""
    global _annotation
    _annotation = factory


def record(name: str, t0: float, t1: float, attrs: dict | None = None) -> None:
    """The plain form: an interval that was not a ``with`` block on one
    thread (``perf_counter`` seconds). Ring only — an annotation cannot be
    opened after the fact."""
    _ring.append((name, t0, t1, attrs))


class span:
    """``with span("batcher.dispatch", program="decode") as sp: ...``;
    ``sp.attrs[...] = ...`` adds what is only known inside the block."""

    __slots__ = ("name", "attrs", "t0", "_ann")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "span":
        ann = _annotation
        self._ann = ann(self.name) if ann is not None else None
        if self._ann is not None:
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        record(self.name, self.t0, t1, self.attrs or None)


def records(t0: float | None = None, t1: float | None = None,
            name: str | None = None) -> list[tuple]:
    """Ring records that overlap ``[t0, t1]`` (all of them by default),
    oldest first, optionally of one name."""
    return [r for r in list(_ring)
            if (name is None or r[0] == name)
            and (t1 is None or r[1] <= t1) and (t0 is None or r[2] >= t0)]


def clear() -> None:
    _ring.clear()
