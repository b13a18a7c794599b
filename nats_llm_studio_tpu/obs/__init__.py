"""Observability: trace context, bounded histograms, events, exposition.

The subsystem PR 1 threads through every layer — see histogram.py,
trace.py, events.py, prom.py, aggregator.py. Import-light on purpose:
nothing here may import jax or the transport (both import *us*); the
fleet aggregator takes an already-connected NATS client by injection.
"""

from .aggregator import (
    Aggregator,
    SloEvaluator,
    SpanStore,
    assemble_trace,
    merge_expositions,
)
from .compile_cache import (
    build_ledger,
    compile_cache_counts,
    install_compile_cache_listener,
)
from .events import EVENTS, EventRing, emit
from .histogram import (
    HistSnapshot,
    LogHistogram,
    MergedHist,
    bucket_pairs,
    merge,
    quantile,
)
from .prom import PromRenderer
from .recorder import FlightRecorder
from .roofline import (
    DECODE_PROGRAMS,
    PREFILL_PROGRAMS,
    SPEC_PROGRAMS,
    WASTE_CATEGORIES,
    HbmLedger,
    classify_program,
    dispatch_shape_key,
    efficiency_enabled,
    program_kind,
    program_kinds,
)
from .trace import (
    STAGES,
    Span,
    Trace,
    new_span_id,
    new_trace_id,
    parse_span_context,
    span_context_value,
)

__all__ = [
    "Aggregator",
    "SloEvaluator",
    "SpanStore",
    "assemble_trace",
    "merge_expositions",
    "EVENTS",
    "EventRing",
    "emit",
    "FlightRecorder",
    "build_ledger",
    "compile_cache_counts",
    "install_compile_cache_listener",
    "HistSnapshot",
    "LogHistogram",
    "MergedHist",
    "bucket_pairs",
    "merge",
    "quantile",
    "PromRenderer",
    "DECODE_PROGRAMS",
    "PREFILL_PROGRAMS",
    "SPEC_PROGRAMS",
    "WASTE_CATEGORIES",
    "HbmLedger",
    "classify_program",
    "program_kind",
    "program_kinds",
    "dispatch_shape_key",
    "efficiency_enabled",
    "STAGES",
    "Span",
    "Trace",
    "new_span_id",
    "new_trace_id",
    "parse_span_context",
    "span_context_value",
]
