"""Efficiency plane: program classes, the device-time ledger's categories, and
HBM ledger reconciliation.

* **Program classification** — ``classify_program`` sorts the names the
  batcher's dispatch timer records (serve/programs.py's table) into prefill,
  decode and other, for the per-request device-time ledger.
* **HBM ledger** — ``HbmLedger`` reconciles the sum of priced memory
  components (weights, block pool, prefix cache, workspace slack) against the
  device allocator's ``bytes_in_use`` on every flight-recorder tick and fires
  an ``hbm_drift`` event when unexplained bytes grow monotonically past a
  threshold: a leak detector for the pool / CoW / handoff paths.

A device roofline is not read here: the benchmark's trace reduction
(benchmark/layer_metrics) divides bytes and operations computed from shapes by
device time from the profiler's trace.

Everything here is host-side accounting: no jax import at module load, no
device work beyond ``memory_stats()``.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Callable

__all__ = [
    "PREFILL_PROGRAMS",
    "DECODE_PROGRAMS",
    "SPEC_PROGRAMS",
    "WASTE_CATEGORIES",
    "classify_program",
    "table_kind",
    "note_programs",
    "program_kind",
    "program_kinds",
    "program_base",
    "program_family",
    "efficiency_enabled",
    "dispatch_shape_key",
    "HbmLedger",
]

# -- program classification ----------------------------------------------------
#
# Names must match the keys the batcher passes to ``BatcherStats.record_program``
# (the table names of serve/programs.py).  Prefill programs are compute-bound,
# decode programs bandwidth-bound.  Anything else (ring compaction, CoW block copies,
# warmup) lands in "other" and is reported as waste unless request-attributed.

PREFILL_PROGRAMS = frozenset(
    {
        "prefill1",
        "prefill_full",
        "admit_fused",
        "admit_many_fused",
        "finish_admit",
        "prefill_chunk_group",
        "select_end",
        "take_rows",
        "finish_admit_group",
        "write_prefix_block",
        "sample_first",
        "admit_fused_paged",
        "admit_many_fused_paged",
        "finish_admit_paged",
        "finish_admit_group_paged",
        "fill_row_chunk",
    }
)

DECODE_PROGRAMS = frozenset(
    {
        "decode",
        "decode_pos",
        "decode_pos_ext",
        "decode_pos_paged",
        "decode_pos_paged_ext",
        # Pallas paged-decode kernel dispatches (serve/batcher.py under
        # DECODE_KERNEL=pallas) — ledgered apart from the decode_pos_paged
        # gather-view path so the roofline can attribute the kernel swap
        "decode_pallas",
        "decode_pallas_ext",
        "spec_verify",
        "spec_verify_paged",
        "spec_verify_pallas",
    }
)

SPEC_PROGRAMS = frozenset(
    {"spec_verify", "spec_verify_paged", "spec_verify_pallas"}
)

# Outcome categories for the device-time ledger.  "other" absorbs dispatches
# with no request context (warmup, compaction, CoW copies).
WASTE_CATEGORIES = (
    "served",
    "shed_after_prefill",
    "cancelled",
    "deadline_abort",
    "spec_rejected",
    "disagg_fallback_reprefill",
    "other",
)


# Program-family suffixes the batcher appends to the base dispatch names:
# ``_moe`` when the forward runs capacity-factor routed MoE (appended at
# wrap time — a property of the model), ``_ring`` when a prefill_full
# dispatch takes the sp ring-attention path (appended per dispatch — a
# property of that prompt's length bucket).  Classification strips them so
# the roofline ledger keeps one prefill/decode split while metrics retain
# the tagged names.
_FAMILY_SUFFIXES = ("_ring", "_moe")


def program_base(name: str) -> str:
    """Strip family suffixes: ``prefill_full_moe_ring`` -> ``prefill_full``."""
    changed = True
    while changed:
        changed = False
        for sfx in _FAMILY_SUFFIXES:
            if name.endswith(sfx) and name[: -len(sfx)]:
                name = name[: -len(sfx)]
                changed = True
    return name


def program_family(name: str) -> str:
    """Coarse family tag for a recorded program name: ``ring_prefill`` when
    the dispatch ran the sequence-parallel ring, ``moe_routed`` when the
    forward used routed experts, ``dense`` otherwise."""
    if name.endswith("_ring") or "_ring_" in name:
        return "ring_prefill"
    if name.endswith("_moe") or "_moe_" in name:
        return "moe_routed"
    return "dense"


def classify_program(name: str) -> str:
    name = program_base(name)
    if name in PREFILL_PROGRAMS:
        return "prefill"
    if name in DECODE_PROGRAMS:
        return "decode"
    return "other"


# -- the kinds of the jitted programs ------------------------------------------
#
# One program has three names: its table name (serve/programs.py, what
# ``lmstudio_program_ms`` and the sets above key on), and its jitted
# function's ``__name__``, under which a device trace (``jit_<name>``) and the
# build ledger (``fun_name``) know it. The two are one word but for the Pallas
# decode entries below; ``program_kind`` is the bridge, for a reader that has
# the trace's name alone (tests/test_scopes.py holds it against every
# family's table).

_TABLE_NAME_OF = {
    "decode_pos_pallas": "decode_pallas",
    "decode_pos_moe": "decode_pallas",
    "decode_pos_pallas_ext": "decode_pallas_ext",
}
_noted: dict[str, str] = {}


def table_kind(name: str) -> str:
    """``prefill`` (chunks, fused admits, finishes, the prefix copies),
    ``decode``, ``spec`` (a verify: ``SPEC_PROGRAMS``, which the device-time
    ledger counts under decode) or ``other``, of a table name."""
    return "spec" if program_base(name) in SPEC_PROGRAMS else classify_program(name)


def program_kind(jit_name: str) -> str:
    """The kind of the program a device trace calls ``jit_<jit_name>``
    (``other`` too for what no table holds: XLA's own small programs)."""
    return table_kind(_TABLE_NAME_OF.get(jit_name, jit_name))


def note_programs(table: dict[str, Callable]) -> None:
    """The jitted programs of a ``build_programs`` table, for the worker's
    page (``lmstudio_program_kind``); idempotent."""
    for name, fn in table.items():
        jit_name = getattr(fn, "__name__", name)
        _noted[jit_name] = program_kind(jit_name)


def program_kinds() -> dict[str, str]:
    """{jit name: kind} of the programs this process built."""
    return dict(_noted)


def efficiency_enabled() -> bool:
    """EFFICIENCY=0|false|off turns the device-time ledger and the HBM ledger off."""
    return os.environ.get("EFFICIENCY", "1").strip().lower() not in ("0", "false", "off", "no")


# -- dispatch shapes -----------------------------------------------------------


def dispatch_shape_key(args: tuple, kwargs: dict) -> tuple:
    """Cheap structural key for a dispatch: shapes/dtypes for arrays, raw values
    for static scalars.  Two dispatches with equal keys hit the same XLA
    executable: a key not seen before is a shape's first dispatch."""

    def sig(a: Any):
        shp = getattr(a, "shape", None)
        if shp is not None:
            return (tuple(shp), str(getattr(a, "dtype", "")))
        if a is None or isinstance(a, (int, float, bool, str)):
            return a
        return type(a).__name__

    kw = tuple(sorted((k, sig(v)) for k, v in kwargs.items())) if kwargs else ()
    return (tuple(sig(a) for a in args), kw)


# -- HBM ledger ----------------------------------------------------------------


def _default_bytes_in_use() -> int | None:
    try:
        import jax

        ms = jax.local_devices()[0].memory_stats()
        if not ms:
            return None
        v = ms.get("bytes_in_use")
        return int(v) if v is not None else None
    except Exception:
        return None


class HbmLedger:
    """Reconcile priced HBM components against the allocator's bytes_in_use.

    ``components`` maps a name to a zero-arg callable returning its current
    priced bytes.  ``tick()`` (called per flight-recorder frame) samples the
    allocator, computes ``unexplained = bytes_in_use - sum(priced)``, and fires
    one ``hbm_drift`` event when unexplained bytes grow monotonically above
    ``drift_threshold_bytes`` (vs. the running baseline) for ``sustain_ticks``
    consecutive samples — then re-baselines so a stable-but-larger footprint
    doesn't alarm forever.  On backends without ``memory_stats`` (CPU) every
    sample is zeros and no event can fire.
    """

    def __init__(
        self,
        components: dict[str, Callable[[], int]],
        *,
        bytes_in_use_fn: Callable[[], int | None] | None = None,
        drift_threshold_bytes: int | None = None,
        sustain_ticks: int = 4,
        emit_fn: Callable[..., Any] | None = None,
    ):
        self.components = dict(components)
        self.bytes_in_use_fn = bytes_in_use_fn or _default_bytes_in_use
        if drift_threshold_bytes is None:
            try:
                drift_threshold_bytes = int(
                    os.environ.get("HBM_DRIFT_THRESHOLD_BYTES", str(64 << 20))
                )
            except ValueError:
                drift_threshold_bytes = 64 << 20
        self.drift_threshold_bytes = int(drift_threshold_bytes)
        self.sustain_ticks = max(int(sustain_ticks), 1)
        self.emit_fn = emit_fn
        self.drift_events = 0
        self._baseline: int | None = None
        self._last_unexplained: int | None = None
        self._grow_ticks = 0
        self._last: dict[str, Any] = {}
        self._lock = threading.Lock()

    def last_sample(self) -> dict[str, Any]:
        with self._lock:
            return dict(self._last)

    def tick(self) -> int:
        """Sample + reconcile; returns current drift-above-baseline bytes (>= 0)."""
        priced: dict[str, int] = {}
        for name, fn in self.components.items():
            try:
                priced[name] = int(fn() or 0)
            except Exception:
                priced[name] = 0
        total = sum(priced.values())
        try:
            in_use = self.bytes_in_use_fn()
        except Exception:
            in_use = None
        if in_use is None:
            sample = {
                "bytes_in_use": 0,
                "priced_bytes": total,
                "unexplained_bytes": 0,
                "drift_bytes": 0,
                "components": priced,
            }
            with self._lock:
                self._last = sample
            return 0
        unexplained = int(in_use) - total
        fire = False
        with self._lock:
            if self._baseline is None:
                self._baseline = unexplained
            growth = unexplained - self._baseline
            monotone = self._last_unexplained is None or unexplained >= self._last_unexplained
            if growth > self.drift_threshold_bytes and monotone:
                self._grow_ticks += 1
            elif not monotone:
                self._grow_ticks = 0
            self._last_unexplained = unexplained
            if self._grow_ticks >= self.sustain_ticks:
                fire = True
                self.drift_events += 1
                self._baseline = unexplained
                self._grow_ticks = 0
            drift = max(growth, 0)
            self._last = {
                "bytes_in_use": int(in_use),
                "priced_bytes": total,
                "unexplained_bytes": unexplained,
                "drift_bytes": drift,
                "components": priced,
            }
        if fire and self.emit_fn is not None:
            try:
                self.emit_fn(
                    "hbm_drift",
                    bytes_in_use=int(in_use),
                    priced_bytes=total,
                    unexplained_bytes=unexplained,
                    growth_bytes=growth,
                )
            except Exception:
                pass
        return drift
