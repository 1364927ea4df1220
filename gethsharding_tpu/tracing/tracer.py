"""Low-overhead span tracer: latency ATTRIBUTION for the period pipeline.

PR 1's serving tier made the hot path asynchronous (admission queue ->
micro-batcher -> double-buffered dispatch), so a slow `verifyAggregates`
can hide in queue wait, batch assembly, or device execution — and a
`metrics.Timer` snapshot cannot say which. This module is the
profiling-first answer (the zkSpeed / Versal-MSM methodology: locate the
bottleneck before optimizing it): spans with monotonic-clock bounds and
tags, a context-local span stack for parent/child attribution, and a
bounded in-memory ring of finished spans served by `/trace` and
exportable as Chrome ``trace_event`` JSON (Perfetto-loadable).

Design constraints, in order:

- **Off means free.** Collection is gated by ONE attribute read
  (`TRACER.enabled`); every producer entry returns a shared no-op span
  without allocating when tracing is off. The serving hot path budgets
  <2% tracer-off overhead (asserted in tests/test_observability.py).
- **Cross-thread spans are explicit.** The context-local stack follows
  one thread of control; the serving pipeline's request lifecycle spans
  THREE threads (caller -> flusher -> dispatch), so those spans are
  recorded with explicit timestamps via `record()` and stitched to the
  caller's trace by the context captured at `submit()` time.
- **Metrics ride along.** Every finished span feeds a
  ``trace/<name>`` timer in the metrics registry, so the influx
  exporter and the dashboard get span-duration percentiles for free.
"""

from __future__ import annotations

import contextvars
import itertools
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

from gethsharding_tpu import metrics

# the active span stack of the current thread of control (contextvars:
# per-thread for plain threads, per-task under asyncio — either way the
# parent of a new span is whatever THIS control flow opened last)
_SPAN_STACK = contextvars.ContextVar("gethsharding_span_stack", default=())


def _id_base() -> int:
    """Per-process id-space offset: trace/span ids now CROSS process
    boundaries (the RPC trace envelope, the merged Chrome export), so
    two replicas both counting from 1 would stitch unrelated requests
    together. The pid in the high bits keeps ids unique across a
    router + N replicas on one host without any coordination.

    Capped below 2^53: the exported JSON is consumed by JavaScript
    (Perfetto), where ids above Number.MAX_SAFE_INTEGER would round
    together and merge unrelated spans. 20 pid bits << 32 tops out at
    ~2^52 and leaves 2^32 ids per process before neighbors overlap."""
    return (os.getpid() & 0xFFFFF) << 32


class Span:
    """One named, tagged interval on the context-local stack."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "start",
                 "end", "tags", "tid", "_tracer", "_token")

    def __init__(self, tracer: "Tracer", name: str, trace_id: int,
                 span_id: int, parent_id: Optional[int], tags: Optional[dict]):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.tags = dict(tags) if tags else {}
        self.tid = threading.get_ident()
        self.start = time.monotonic()
        self.end: Optional[float] = None
        self._tracer = tracer
        self._token = None

    def tag(self, **tags) -> "Span":
        self.tags.update(tags)
        return self

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is not None:
            self.tags.setdefault("error", repr(exc))
        self._tracer.finish(self)
        return False


class _NoopSpan:
    """The shared disabled-path span: no allocation, no clock reads."""

    __slots__ = ()
    trace_id = None
    span_id = None

    def tag(self, **tags) -> "_NoopSpan":
        return self

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


NOOP_SPAN = _NoopSpan()


class Tracer:
    """Span collector: context stack + bounded finished-span ring.

    The ring holds FINISHED span records (plain dicts, newest-last);
    `/trace` groups them into traces on read. Bounded by `ring_spans`,
    so a long-running node holds a recent window, never unbounded
    memory — the go-metrics "cheap enough to leave on" contract.
    """

    def __init__(self, ring_spans: int = 4096,
                 registry: metrics.Registry = metrics.DEFAULT_REGISTRY):
        self.enabled = False
        self.registry = registry
        self._ring: deque = deque(maxlen=ring_spans)
        self._ids = itertools.count(_id_base() + 1)
        self._lock = threading.Lock()
        self._timers: Dict[str, metrics.Timer] = {}
        self._dropped: Optional[metrics.Counter] = None
        self._export_dropped_m: Optional[metrics.Counter] = None
        self._pressure: Optional[metrics.Gauge] = None
        self.spans_recorded = 0
        self.spans_dropped = 0
        # the export plane's staging buffer (fleettrace): None until a
        # SpanExporter enables it — processes that never export pay
        # nothing. Evictions here are counted separately from the
        # display ring's: a span the /trace ring overwrote may still
        # have been exported, and vice versa.
        self._export: Optional[deque] = None
        self.export_dropped = 0

    # -- configuration ------------------------------------------------------

    def configure(self, ring_spans: Optional[int] = None,
                  registry: Optional[metrics.Registry] = None) -> None:
        with self._lock:
            if ring_spans is not None:
                self._ring = deque(self._ring, maxlen=ring_spans)
            if registry is not None:
                self.registry = registry
                self._timers = {}
                self._dropped = None
                self._export_dropped_m = None
                self._pressure = None

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            if self._export is not None:
                self._export.clear()

    # -- export plane (fleettrace) ------------------------------------------

    def enable_export(self, buffer_spans: int = 8192) -> None:
        """Open the export staging buffer: every finished span is also
        queued for a `SpanExporter` to drain. Bounded — if the exporter
        falls behind, the oldest staged spans are evicted and counted
        (`export_dropped` / ``trace/export_dropped``) so shipped batches
        can carry an honest drop count. Idempotent."""
        with self._lock:
            if self._export is None:
                self._export = deque(maxlen=max(1, int(buffer_spans)))

    def disable_export(self) -> None:
        with self._lock:
            self._export = None

    @property
    def export_enabled(self) -> bool:
        return self._export is not None

    def drain_export(self, max_spans: int = 512) -> Tuple[List[dict], int]:
        """Destructively drain up to `max_spans` staged records (oldest
        first). Returns ``(batch, dropped)`` where `dropped` is the
        CUMULATIVE count of spans this process finished but can no
        longer ship (export-buffer evictions) — exporters stamp it on
        every batch so the collector can mark the traces it assembles
        from this source as incomplete rather than presenting a
        truncated tree as the whole request."""
        with self._lock:
            if self._export is None:
                return [], self.export_dropped
            take = min(int(max_spans), len(self._export))
            batch = [self._export.popleft() for _ in range(take)]
            return batch, self.export_dropped

    # -- producer API -------------------------------------------------------

    def new_trace_id(self) -> int:
        return next(self._ids)

    def start(self, name: str, tags: Optional[dict] = None,
              ctx: Optional[Tuple[int, int]] = None):
        """Open a span under the context's current span (a new trace when
        there is none). Returns NOOP_SPAN when disabled — callers use the
        result as a context manager either way.

        An explicit `ctx` — a ``(trace_id, span_id)`` pair from ANOTHER
        process's tracer, carried on the RPC trace envelope — wins over
        the local stack: the new span adopts the remote trace id and
        parents under the remote span, which is how a request traced in
        the router stitches into the replica's handler/dispatch spans."""
        if not self.enabled:
            return NOOP_SPAN
        stack = _SPAN_STACK.get()
        if ctx is not None and ctx[0] is not None:
            trace_id, parent_id = int(ctx[0]), ctx[1]
            parent_id = None if parent_id is None else int(parent_id)
        else:
            parent = stack[-1] if stack else None
            trace_id = parent.trace_id if parent else self.new_trace_id()
            parent_id = parent.span_id if parent else None
        span = Span(self, name, trace_id=trace_id,
                    span_id=self.new_trace_id(),
                    parent_id=parent_id, tags=tags)
        span._token = _SPAN_STACK.set(stack + (span,))
        return span

    def finish(self, span: Span, end: Optional[float] = None) -> None:
        """Close `span` now, or at `end` where the caller read the clock
        itself (`tracing.stage`: span and timer share one reading)."""
        if span._token is not None:
            try:
                _SPAN_STACK.reset(span._token)
            except ValueError:
                pass  # finished from another context: keep the record
            span._token = None
        span.end = time.monotonic() if end is None else end
        self._record(span.name, span.trace_id, span.span_id, span.parent_id,
                     span.start, span.end, span.tags, span.tid)

    def record(self, name: str, start: float, end: float,
               trace_id: Optional[int] = None,
               parent_id: Optional[int] = None,
               tags: Optional[dict] = None,
               tid: Optional[int] = None,
               span_id: Optional[int] = None) -> Optional[int]:
        """Record a completed span from explicit monotonic timestamps —
        the cross-thread form the serving pipeline uses (a request's
        lifecycle spans caller, flusher and dispatch threads; no one
        context owns it). Returns the span id (None when disabled).
        `span_id` is an id taken from `new_trace_id()` beforehand, by a
        producer whose children had to name their parent before the
        parent's end was known."""
        if not self.enabled:
            return None
        if span_id is None:
            span_id = self.new_trace_id()
        self._record(name, trace_id or self.new_trace_id(), span_id,
                     parent_id, start, end, dict(tags) if tags else {},
                     threading.get_ident() if tid is None else tid)
        return span_id

    def current(self) -> Optional[Tuple[int, int]]:
        """(trace_id, span_id) of the context's active span, or None."""
        stack = _SPAN_STACK.get()
        if not stack:
            return None
        top = stack[-1]
        return (top.trace_id, top.span_id)

    # -- sink ---------------------------------------------------------------

    def _record(self, name, trace_id, span_id, parent_id, start, end,
                tags, tid) -> None:
        record = {
            "name": name, "trace": trace_id, "span": span_id,
            "parent": parent_id, "start": start, "end": end,
            "dur_us": round((end - start) * 1e6, 1), "tid": tid,
            "tags": tags,
        }
        timer = self._timers.get(name)
        if timer is None:
            timer = self.registry.timer(f"trace/{name}")
            with self._lock:
                self._timers[name] = timer
        timer.observe(end - start)
        # append under the lock: recent_spans() list()s the deque under
        # it, and an unlocked concurrent append would raise "deque
        # mutated during iteration" mid-scrape
        with self._lock:
            dropped = len(self._ring) == self._ring.maxlen
            self._ring.append(record)
            self.spans_recorded += 1
            if dropped:
                # the ring just overwrote a finished span nobody
                # exported: ring overflow used to be invisible —
                # `trace/dropped` makes an undersized --trace-ring an
                # alert instead of a silently truncated export
                self.spans_dropped += 1
                if self._dropped is None:
                    self._dropped = self.registry.counter("trace/dropped")
                self._dropped.inc()
            if self._pressure is None:
                self._pressure = self.registry.gauge("trace/ring_pressure")
            self._pressure.set(len(self._ring) / (self._ring.maxlen or 1))
            if self._export is not None:
                if len(self._export) == self._export.maxlen:
                    # exporter is behind: evict oldest, keep the count —
                    # the drop rides out on the next batch's envelope
                    self.export_dropped += 1
                    if self._export_dropped_m is None:
                        self._export_dropped_m = self.registry.counter(
                            "trace/export_dropped")
                    self._export_dropped_m.inc()
                self._export.append(record)

    # -- consumer API -------------------------------------------------------

    def recent_spans(self, limit: Optional[int] = None) -> List[dict]:
        """Finished span records, oldest first."""
        with self._lock:
            spans = list(self._ring)
        return spans if limit is None else spans[-limit:]

    def recent_traces(self, limit: int = 100) -> List[dict]:
        """Finished spans grouped into traces, newest trace first."""
        by_trace: Dict[int, List[dict]] = {}
        for record in self.recent_spans():
            by_trace.setdefault(record["trace"], []).append(record)
        traces = sorted(
            by_trace.items(),
            key=lambda item: max(r["end"] for r in item[1]), reverse=True)
        return [{"trace_id": trace_id,
                 "duration_us": round(
                     (max(r["end"] for r in spans)
                      - min(r["start"] for r in spans)) * 1e6, 1),
                 "spans": spans}
                for trace_id, spans in traces[:limit]]


# THE process tracer (the metrics.DEFAULT_REGISTRY analog): instrumented
# code records here; `--trace` / tracing.enable() turn collection on.
TRACER = Tracer()


def enable(ring_spans: int = 4096,
           registry: Optional[metrics.Registry] = None) -> Tracer:
    TRACER.configure(ring_spans=ring_spans, registry=registry)
    TRACER.enabled = True
    return TRACER


def disable() -> None:
    TRACER.enabled = False


def span(name: str, ctx: Optional[Tuple[int, int]] = None, **tags):
    """Open a context-stacked span on the process tracer (no-op when
    disabled). Use as ``with tracing.span("notary/fetch"):``. `ctx`
    adopts a remote (trace_id, span_id) — see `Tracer.start`."""
    if not TRACER.enabled:
        return NOOP_SPAN
    return TRACER.start(name, tags or None, ctx=ctx)


def tag_current(**tags) -> None:
    """SET tags on the context's innermost active span, last writer
    wins (the non-numeric sibling of `tag_current_add`: ids, names,
    labels). No-op when tracing is off or no span is open."""
    if not TRACER.enabled:
        return
    stack = _SPAN_STACK.get()
    if not stack:
        return
    stack[-1].tags.update(tags)


def tag_current_add(**tags) -> None:
    """SUM numeric tags into the context's innermost ACTIVE span (no-op
    when tracing is off or no span is open) — lets a callee annotate
    its caller's span without threading span objects through the API.
    The sig backend stamps per-dispatch wire bytes and device-cache hit
    bytes onto the notary's enclosing ``notary/audit`` span this way;
    accumulation (not last-writer-wins) makes a span covering several
    dispatches (a K-period overlapped audit) report TOTALS."""
    if not TRACER.enabled:
        return
    stack = _SPAN_STACK.get()
    if not stack:
        return
    span_tags = stack[-1].tags
    for key, value in tags.items():
        span_tags[key] = span_tags.get(key, 0) + value


def request_context() -> Optional[Tuple[int, int]]:
    """The serving hot path's ONE producer-side guard: the caller's
    (trace_id, span_id) to stitch a cross-thread request to, or None.
    Exactly one attribute read when tracing is off — the cost the <2%
    overhead budget is measured against."""
    if not TRACER.enabled:
        return None
    return TRACER.current()


# the wire-propagation name: what `RPCClient.call` ships on the JSON-RPC
# trace envelope is exactly the serving tier's stitching context
current_context = request_context


# == log <-> trace correlation =============================================


class TraceContextFilter:
    """`logging.Filter`-shaped stamp: every record gets the emitting
    context's trace/span id (``-`` when none), so a warning from
    ``sharding.node`` joins against ``/trace`` output by id instead of
    by eyeballing timestamps. Costs one contextvar read per record;
    with tracing disabled the stack is always empty and the stamp is
    the constant ``-``."""

    def filter(self, record) -> bool:
        stack = _SPAN_STACK.get()
        if stack:
            top = stack[-1]
            record.trace_id = str(top.trace_id)
            record.span_id = str(top.span_id)
        else:
            record.trace_id = "-"
            record.span_id = "-"
        return True


LOG_FILTER = TraceContextFilter()


def install_log_correlation() -> None:
    """Attach the trace-context filter to every root handler (filters
    on the root LOGGER don't see child-logger records; handlers do —
    stdlib logging's propagation rule). Idempotent; the composition
    roots (node CLI, chain_server) call it right after basicConfig,
    whose format strings reference ``%(trace_id)s``."""
    import logging

    for handler in logging.getLogger().handlers:
        if LOG_FILTER not in handler.filters:
            handler.addFilter(LOG_FILTER)
