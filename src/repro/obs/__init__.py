"""Observability: tracing, metrics, exporters (the stack's joining view).

* :mod:`repro.obs.trace` — spans with injectable clocks and explicit
  cross-network parenting, kept in memory or mirrored into the JAX
  profiler's trace; disabled mode is a strict no-op.
* :mod:`repro.obs.metrics` — counters/gauges/fixed-bucket histograms plus
  adapters lifting the existing per-layer stat structs into uniformly
  named metrics.
* :mod:`repro.obs.export` — Chrome trace-event files (flamegraphs), with
  span-tree integrity helpers.
"""
from .trace import (NO_SPAN, NULL_TRACER, NullTracer, Span, TraceContext,
                    Tracer)
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      lift_ae_stats, lift_dispatch_stats, lift_io_stats,
                      lift_network, lift_query_stats, lift_struct)
from .export import span_trees, spans_to_chrome, tree_names, write_chrome_trace

__all__ = [
    "Tracer", "NullTracer", "NULL_TRACER", "NO_SPAN", "Span", "TraceContext",
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "lift_struct", "lift_io_stats", "lift_query_stats", "lift_ae_stats",
    "lift_network", "lift_dispatch_stats",
    "spans_to_chrome", "write_chrome_trace", "span_trees", "tree_names",
]
