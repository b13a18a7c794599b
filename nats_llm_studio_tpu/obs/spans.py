"""Host spans on the device trace's clock, the one ring that keeps them, and
the names inside the jitted programs (``SCOPE_NAMES``, further down: the other
half of one vocabulary, on the device's side of the same trace).

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

# every ``jax.named_scope`` the five model files, ``models/experts.py`` and
# the step programs open, as the path it leaves in an operation's ``op_name``
# (``jit(decode_pos_pallas)/while/body/closed_call/seq/attn/dot_general``).
# Two levels at most, the same words in every family. A top-level word is
# opened in the layer body, around the norm that feeds the block and the
# residual add that takes it; a second-level word inside it. What lies under
# none of them is glue (the readers' word, not a scope): layout copies on
# entry to a program, casts, table and position updates, scan plumbing, the
# pool writes of the admits. A scope is metadata on the lowered operations: it
# changes no operation of any compiled program (tests/test_scopes.py)
SCOPE_NAMES = (
    "embed",         # token embedding: the residual stream's (or streams') start
    "seq",           # everything that mixes positions, with the norm before and the add after
    "seq/attn",      # GQA: q/k/v/o, rotary, the KV write, the paged / flash kernel or its XLA form
    "seq/window",    # the same over a ring of the last ``window`` keys (models/swa_moe.py)
    "seq/mla",       # latent attention: down and up projections, the latent write, absorbed or expanded
    "seq/ssm",       # Mamba-2: in-projection, convolution, scan or ssm_state_step, gated norm, out
    "seq/linear",    # gated delta rule: in-projections, convolution, chunked rule or gdn_step_inputs + gated_delta_step, gated norm, out; Lightning: projections, norms, rotary, chunked rule or lightning_step, output norm, gate, out
    "seq/sparse",    # block-sparse attention (models/sala.py): projections, norms, the KV write, the picked walk or masked attention, gate, out
    "seq/sparse/pool",    # ... the pooled keys a chunk or a step completes, written beside the cache
    "seq/sparse/select",  # ... scores against the pooled keys, the group's softmax, block maxima, top-k, the picked table
    "ffn",           # the position-wise block, with the norm before and the add after
    "ffn/mlp",       # the dense SwiGLU
    "ffn/router",    # scores, top-k, gates, the expert counters
    "ffn/experts",   # the routed experts in every form (hit_list, grouped, dense)
    "ffn/shared",    # the always-on expert(s)
    "ffn/latent_down",  # hidden -> the latent the routed experts work in (models/experts.py, cfg.moe_latent)
    "ffn/latent_up",    # ... the routed sum back to the hidden width, added to the shared expert's
    "mix",           # the four-stream maps, read, write and hc_sinkhorn of models/mla_moe.py
    "head",          # what turns the last hidden state into a token
    "head/logits",   # final norm and lm_head (a prefill's: one row a prompt)
    "head/sample",   # temperature, top-k, top-p, seeds, acceptance, the token write
)
_SCOPE_TOPS = frozenset(s for s in SCOPE_NAMES if "/" not in s)


def scope_of(op_name: str) -> str | None:
    """The entry of ``SCOPE_NAMES`` an operation's ``op_name`` lies under:
    its first top-level word and, where the next word (or two) makes a listed
    path with it, the longest such path. None for glue."""
    parts = op_name.split("/")
    for i, word in enumerate(parts):
        if word in _SCOPE_TOPS:
            for depth in (3, 2):
                path = "/".join(parts[i:i + depth])
                if path in SCOPE_NAMES:
                    return path
            return word
    return None


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
