"""Compute-efficiency plane: roofline accounting and HBM ledger reconciliation.

Three small, dependency-light pieces that the batcher / registry / worker wire
together into MFU / MBU / goodput metrics:

* **Program cost extraction** — ``extract_dispatch_cost`` pulls flops and
  bytes-accessed out of XLA's cost analysis for a jitted program *before* it is
  dispatched (programs use ``donate_argnums``, so inputs are invalid after the
  call).  Results are cached per (program, shape-bucket) by the batcher's timer
  wrapper; any failure caches ``None`` forever so serving never pays twice.
* **Chip peak table** — ``chip_peaks`` resolves peak bf16 FLOP/s and HBM
  bytes/s for the local accelerator (v4 / v5e / v5p / v6e), overridable with
  ``TPU_PEAK_FLOPS`` / ``TPU_HBM_GBPS``, with a deliberately modest CPU
  fallback so smoke runs still report nonzero MFU / MBU.
* **HBM ledger** — ``HbmLedger`` reconciles the sum of priced memory
  components (weights, block pool, prefix cache, workspace slack) against the
  device allocator's ``bytes_in_use`` on every flight-recorder tick and fires
  an ``hbm_drift`` event when unexplained bytes grow monotonically past a
  threshold: a leak detector for the pool / CoW / handoff paths.

Everything here is host-side accounting: no jax import at module load, no
device work beyond ``memory_stats()`` / one-time ``lower()`` calls.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Any, Callable

__all__ = [
    "PREFILL_PROGRAMS",
    "DECODE_PROGRAMS",
    "SPEC_PROGRAMS",
    "WASTE_CATEGORIES",
    "classify_program",
    "program_base",
    "program_family",
    "efficiency_enabled",
    "chip_peaks",
    "resolve_chip_peaks",
    "extract_dispatch_cost",
    "dispatch_shape_key",
    "RollingUtilization",
    "HbmLedger",
]

# -- program classification ----------------------------------------------------
#
# Names must match the keys the batcher passes to ``BatcherStats.record_program``
# (the ``_timed`` wrapper names in serve/batcher.py).  Prefill programs are
# compute-bound (MFU is the headline); decode programs are bandwidth-bound
# (MBU is the headline).  Anything else (ring compaction, CoW block copies,
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


def efficiency_enabled() -> bool:
    """EFFICIENCY=0|false|off kills the whole plane (cost extraction + ledger)."""
    return os.environ.get("EFFICIENCY", "1").strip().lower() not in ("0", "false", "off", "no")


# -- chip peak table -----------------------------------------------------------
#
# (substring of jax device_kind, peak bf16 FLOP/s, peak HBM bytes/s).  Matched
# case-insensitively, first hit wins, so more specific kinds come first.

_CHIP_PEAKS: tuple[tuple[str, float, float], ...] = (
    ("v6e", 918e12, 1640e9),
    ("v5p", 459e12, 2765e9),
    ("v5e", 197e12, 819e9),
    ("v5 lite", 197e12, 819e9),
    ("v5litepod", 197e12, 819e9),
    ("v4", 275e12, 1228e9),
)

# CPU fallback so smoke/bench runs on the CPU backend still produce nonzero
# (if not meaningful) MFU/MBU.  Deliberately modest: ~0.5 TFLOP/s, 50 GB/s.
_CPU_PEAKS = (5e11, 5e10)

_peaks_lock = threading.Lock()
_peaks_cache: tuple[float, float] | None = None


def resolve_chip_peaks(device_kind: str, platform: str = "") -> tuple[float, float]:
    """Pure lookup: (peak_flops_per_s, peak_hbm_bytes_per_s) for a device kind.

    Env overrides win over the table. On platform ``tpu`` a kind that
    matches no row raises, naming the kind — a utilization against made-up
    peaks is worse than none; elsewhere unknown kinds get the CPU fallback.
    ``TPU_PEAK_FLOPS`` is raw FLOP/s; ``TPU_HBM_GBPS`` is GB/s (decimal).
    """
    flops = bw = 0.0
    kind = (device_kind or "").lower()
    for sub, f, b in _CHIP_PEAKS:
        if sub in kind:
            flops, bw = f, b
            break
    else:
        if platform == "tpu":
            raise ValueError(
                f"no peak FLOP/s and HBM bytes/s known for TPU device_kind "
                f"{device_kind!r}; add a row to obs/roofline.py _CHIP_PEAKS"
            )
        flops, bw = _CPU_PEAKS
    try:
        env_f = os.environ.get("TPU_PEAK_FLOPS")
        if env_f:
            flops = float(env_f)
    except ValueError:
        pass
    try:
        env_b = os.environ.get("TPU_HBM_GBPS")
        if env_b:
            bw = float(env_b) * 1e9
    except ValueError:
        pass
    return (max(flops, 1.0), max(bw, 1.0))


def chip_peaks() -> tuple[float, float]:
    """Resolve and cache peaks for the local jax backend (lazy). Raises on
    a TPU whose device_kind has no row (``resolve_chip_peaks``)."""
    global _peaks_cache
    with _peaks_lock:
        if _peaks_cache is not None:
            return _peaks_cache
    import jax

    dev = jax.devices()[0]
    peaks = resolve_chip_peaks(dev.device_kind, dev.platform)
    with _peaks_lock:
        _peaks_cache = peaks
    return peaks


def _reset_peaks_cache() -> None:  # test hook
    global _peaks_cache
    with _peaks_lock:
        _peaks_cache = None


# -- per-program cost extraction -----------------------------------------------


def dispatch_shape_key(args: tuple, kwargs: dict) -> tuple:
    """Cheap structural key for a dispatch: shapes/dtypes for arrays, raw values
    for static scalars.  Two dispatches with equal keys hit the same XLA
    executable, so their cost analysis is identical."""

    def sig(a: Any):
        shp = getattr(a, "shape", None)
        if shp is not None:
            return (tuple(shp), str(getattr(a, "dtype", "")))
        if a is None or isinstance(a, (int, float, bool, str)):
            return a
        return type(a).__name__

    kw = tuple(sorted((k, sig(v)) for k, v in kwargs.items())) if kwargs else ()
    return (tuple(sig(a) for a in args), kw)


def extract_dispatch_cost(fn: Any, args: tuple, kwargs: dict) -> tuple[float, float] | None:
    """(flops, bytes_accessed) for one dispatch of a jitted ``fn``, or None.

    Must run *before* the dispatch: programs donate input buffers, which are
    invalid afterwards.  Uses ``lowered.cost_analysis()`` ONLY — no backend
    compile: ``lowered.compile()`` would not populate the jit's own
    executable cache, so probing through it would pay every program's
    compile twice.  A program whose analysis reads all-zero is simply not
    costed (callers cache the None).  Never raises.
    """
    try:
        lowered = fn.lower(*args, **kwargs)
    except Exception:
        return None

    def _pick(ca: Any) -> tuple[float, float]:
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if not isinstance(ca, dict):
            return (0.0, 0.0)
        try:
            f = float(ca.get("flops", 0.0) or 0.0)
        except (TypeError, ValueError):
            f = 0.0
        try:
            b = float(ca.get("bytes accessed", 0.0) or 0.0)
        except (TypeError, ValueError):
            b = 0.0
        return (f, b)

    flops = bytes_ = 0.0
    try:
        flops, bytes_ = _pick(lowered.cost_analysis())
    except Exception:
        return None
    if flops <= 0.0 and bytes_ <= 0.0:
        return None
    return (max(flops, 0.0), max(bytes_, 0.0))


# -- rolling utilization -------------------------------------------------------


class RollingUtilization:
    """Flops/bytes over a rolling wall-clock window → achieved rates.

    ``add`` is called from the batcher owner thread per dispatch; ``rates`` /
    ``utilization`` from scrape threads, hence the lock.  The denominator is
    wall time spanned by the retained samples (standard MFU definition), not
    summed host dispatch time — with the async dispatch pipeline the latter
    wildly overstates utilization.
    """

    def __init__(self, window_s: float = 10.0, clock: Callable[[], float] = time.monotonic):
        self.window_s = float(window_s)
        self.clock = clock
        self._dq: deque[tuple[float, float, float]] = deque()
        self._lock = threading.Lock()

    def add(self, flops: float, bytes_: float) -> None:
        now = self.clock()
        with self._lock:
            self._dq.append((now, float(flops), float(bytes_)))
            cutoff = now - self.window_s
            while self._dq and self._dq[0][0] < cutoff:
                self._dq.popleft()

    def rates(self) -> tuple[float, float]:
        """(flops_per_s, bytes_per_s) over the window; zeros when idle."""
        now = self.clock()
        with self._lock:
            cutoff = now - self.window_s
            while self._dq and self._dq[0][0] < cutoff:
                self._dq.popleft()
            if not self._dq:
                return (0.0, 0.0)
            span = now - self._dq[0][0]
            if span <= 0.0:
                return (0.0, 0.0)
            fl = sum(s[1] for s in self._dq)
            by = sum(s[2] for s in self._dq)
        return (fl / span, by / span)

    def utilization(self, peaks: tuple[float, float] | None = None) -> tuple[float, float]:
        """(mfu, mbu) in [0, 1] against chip peaks (clamped at 1.0)."""
        pf, pb = peaks if peaks is not None else chip_peaks()
        rf, rb = self.rates()
        return (min(rf / max(pf, 1.0), 1.0), min(rb / max(pb, 1.0), 1.0))


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
