"""Unified observability: metrics registry + span tracing + exporters.

The layer every subsystem reports through (docs/observability.md):

  * :mod:`repro.obs.metrics` — Counter/Gauge/Histogram families in a
    process-wide registry, cheap enough for host-side hot loops;
  * :mod:`repro.obs.trace` — bounded-ring span tracer emitting Chrome
    Trace Event Format JSON (Perfetto / chrome://tracing), and ``region``,
    a live host span on the profiler's clock that feeds the registry's
    span counters (and the ring, given a handle);
  * :mod:`repro.obs.export` — Prometheus text exposition, periodic
    flusher;
  * :mod:`repro.obs.stats_util` — empty-safe percentile/summary helpers
    shared by ``ServeEngine.stats()`` and the benches.

``Observability`` bundles one registry + one tracer so instrumented
subsystems (``ServeEngine(obs=...)``, ``train_loop(obs=...)``) take a
single handle, and the launch CLIs build one from ``--trace-out`` /
``--metrics-out`` flags.
"""
from __future__ import annotations

from typing import Optional

from .export import PeriodicFlusher, parse_prometheus_text, prometheus_text
from .metrics import (
    DEFAULT_BUCKETS,
    REGISTRY,
    Counter,
    Family,
    Gauge,
    Histogram,
    MetricsRegistry,
    exponential_buckets,
    jit_retraces,
)
from .stats_util import median, median_by, percentile, summarize
from .trace import SpanTracer, region

__all__ = [
    "Observability",
    "MetricsRegistry",
    "Counter",
    "Gauge",
    "Histogram",
    "Family",
    "REGISTRY",
    "DEFAULT_BUCKETS",
    "exponential_buckets",
    "jit_retraces",
    "SpanTracer",
    "region",
    "prometheus_text",
    "parse_prometheus_text",
    "PeriodicFlusher",
    "percentile",
    "median",
    "median_by",
    "summarize",
]


class Observability:
    """One registry + one tracer, passed as a single handle.

    ``metrics=None`` uses the process-wide :data:`REGISTRY` (the CLI
    default — one exposition file covers everything in the process);
    tests and benches pass a fresh ``MetricsRegistry()`` to isolate.
    """

    def __init__(self, *, metrics: Optional[MetricsRegistry] = None,
                 trace_capacity: int = 65536, pid: int = 0,
                 process_name: Optional[str] = None):
        self.metrics = metrics if metrics is not None else REGISTRY
        self.trace = SpanTracer(
            capacity=trace_capacity, pid=pid, process_name=process_name
        )

    def flusher(self, *, metrics_path=None, trace_path=None,
                interval: float = 5.0) -> PeriodicFlusher:
        """A PeriodicFlusher wired to this bundle's registry and tracer."""
        return PeriodicFlusher(
            registry=self.metrics, tracer=self.trace,
            metrics_path=metrics_path, trace_path=trace_path,
            interval=interval,
        )
