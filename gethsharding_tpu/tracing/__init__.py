"""Span-structured tracing for the period pipeline.

Latency attribution across the asynchronous hot path (the serving
tier's queue -> batcher -> dispatch lifecycle) and the actor loops
around it:

- ``tracer.py`` — the span tracer: context-local span stack,
  monotonic-clock spans with tags, a bounded ring of finished spans,
  span-duration timers folded into the metrics registry, and an
  off-means-one-attribute-read enable gate.
- ``stage.py`` — the stage clock: one timed leaf of a request as a
  registry timer (always), a span (tracer on) and a
  ``jax.profiler.TraceAnnotation`` (JAX imported), plus the collector's
  pause counter (``GC_CLOCK``).
- ``export.py`` — Chrome ``trace_event`` JSON export
  (Perfetto-loadable; the ``--trace-out`` artifact).

Surfaces: ``GET /trace`` on the node StatusServer (recent traces),
``--trace`` / ``--trace-out`` / ``--trace-ring`` on the sharding CLI,
and ``trace/<span-name>`` timers on ``/metrics`` + the influx exporter.
"""

from gethsharding_tpu.tracing.export import (
    chrome_trace_events,
    clock_offset_us,
    write_chrome_trace,
)
from gethsharding_tpu.tracing.stage import GC_CLOCK, annotation, stage
from gethsharding_tpu.tracing.tracer import (
    LOG_FILTER,
    NOOP_SPAN,
    Span,
    TRACER,
    TraceContextFilter,
    Tracer,
    current_context,
    disable,
    enable,
    install_log_correlation,
    request_context,
    span,
    tag_current,
    tag_current_add,
)

__all__ = [
    "GC_CLOCK",
    "LOG_FILTER",
    "NOOP_SPAN",
    "Span",
    "TRACER",
    "TraceContextFilter",
    "Tracer",
    "annotation",
    "chrome_trace_events",
    "clock_offset_us",
    "current_context",
    "disable",
    "enable",
    "install_log_correlation",
    "request_context",
    "span",
    "stage",
    "tag_current",
    "tag_current_add",
    "write_chrome_trace",
]
