"""repro.obs — unified observability: metrics registry + tracing spans.

One process-wide :class:`MetricsRegistry` (counters / gauges / log-bucket
histograms, Prometheus text dump) and one :func:`trace_span` API (nested
host-side spans, JSONL ring-buffer export, started on the profiler's
clock) with host pauses — collections, XLA compiles, persistent-cache loads
— recorded as spans too (:func:`install_pause_hooks`).  Every tier — resilience
sessions, executors, serving, streaming, training, autotune — records
through here; ``tools/obs_report.py`` / ``make obs-report`` renders both.

Everything in this package is host-side Python: no jax imports at module
scope, nothing obs does ever runs inside a compiled step.
"""

from .metrics import (
    Counter,
    Gauge,
    Histogram,
    HistogramSnapshot,
    MetricsRegistry,
    StatsView,
    default_registry,
    log_bounds,
    percentile,
    set_default_registry,
)
from .pauses import install_pause_hooks
from .trace import (
    Span,
    TraceBuffer,
    configure_buffer,
    default_buffer,
    export_jsonl,
    flush,
    obs_enabled,
    profiler_enabled,
    set_clock,
    trace_span,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "HistogramSnapshot",
    "MetricsRegistry",
    "Span",
    "StatsView",
    "TraceBuffer",
    "configure_buffer",
    "default_buffer",
    "default_registry",
    "export_jsonl",
    "flush",
    "install_pause_hooks",
    "log_bounds",
    "obs_enabled",
    "percentile",
    "profiler_enabled",
    "set_clock",
    "set_default_registry",
    "trace_span",
]
