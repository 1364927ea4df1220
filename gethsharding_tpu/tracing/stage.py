"""Stage clocks: one timed leaf of a request, seen three ways at once.

A stage is a stretch in which one thread works, or waits on the device,
for one request: the server's JSON parse, the handler's decode, the
dispatch's host marshal, transfer, launch, wait and pull. `stage` times
it where it happens and reports it

- to the registry timer it is given, always (two clock reads and one
  `Timer.observe`), so a `shard_metrics` snapshot holds the stage's mean
  with tracing off, which is how it is measured;
- to the tracer as a span of the same name under the context's current
  span, when `TRACER.enabled`, with the timer's own two readings as its
  bounds, so span and timer agree;
- to the JAX profiler as a `TraceAnnotation`, when JAX is already
  imported in the process, so that whenever a device trace is running
  the stage lies on a host plane of that trace, on the profiler's clock.

Only leaves are stages. An enclosing span (`rpc/<method>`,
`serving/<label>/dispatch`) or a parked one (a handler waiting on its
future) is never annotated: a reducer that labels a device gap with the
host span overlapping it longest would hand every gap to the enclosure.

The collector is the one stage no code enters: `GC_CLOCK.install()`
hangs a callback on `gc.callbacks` that counts the microseconds the
process spent inside collections. A serving process also hands it its
compile watch (`settle_after=`): after each first compile the clock
settles the heap, so that a full collection stops walking what the
compile left behind.
"""

from __future__ import annotations

import contextlib
import gc
import sys
import threading
import time
from collections import deque
from typing import Optional, Tuple

from gethsharding_tpu import metrics
from gethsharding_tpu.tracing.tracer import (NOOP_SPAN, TRACER,
                                             _SPAN_STACK)


_NO_ANNOTATION = contextlib.nullcontext()


def annotation(name: str):
    """A `jax.profiler.TraceAnnotation` for `name`, or a context manager
    that does nothing in a process that has not imported JAX: a stage
    never imports it (the process that does holds the chip). The
    annotation costs a flag read while no trace is running. For a leaf
    that `stage` cannot wrap because its name is known only afterwards
    (the server's parse, before the method is read)."""
    profiler = sys.modules.get("jax.profiler")
    # a module is in sys.modules before its body has run: another thread
    # may be half way through `import jax`
    cls = getattr(profiler, "TraceAnnotation", None)
    return _NO_ANNOTATION if cls is None else cls(name)


class stage:
    """``with tracing.stage("sig/transfer_time", timer):`` (see the
    module docstring). `seconds` holds the reading afterwards. `ctx` is
    a ``(trace_id, span_id)`` to parent the span under in place of the
    context's current span (`Tracer.start`); `tags` go on the span."""

    __slots__ = ("name", "timer", "seconds", "_ctx", "_tags", "_t0",
                 "_span", "_note")

    def __init__(self, name: str, timer: metrics.Timer,
                 ctx: Optional[Tuple[int, int]] = None,
                 tags: Optional[dict] = None):
        self.name = name
        self.timer = timer
        self.seconds = 0.0
        self._ctx = ctx
        self._tags = tags

    def __enter__(self) -> "stage":
        self._span = TRACER.start(self.name, self._tags, ctx=self._ctx)
        self._note = annotation(self.name)
        self._note.__enter__()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        end = time.monotonic()
        self._note.__exit__(exc_type, exc, tb)
        self.seconds = end - self._t0
        self.timer.observe(self.seconds)
        span = self._span
        if span is not NOOP_SPAN:   # what `start` gives with the tracer off
            span.start = self._t0
            if exc_type is not None:
                span.tags.setdefault("error", repr(exc))
            TRACER.finish(span, end=end)
            GC_CLOCK.flush_spans()
        return False


# == the collector ==========================================================


class _PauseCounter(metrics.Counter):
    """A counter only the collector's callback writes. The callback runs
    on whatever thread tripped the collection, at any bytecode of it,
    also inside `rate_1m`'s locked region of this very counter during a
    snapshot: `inc` must take no lock. Collections do not nest and run
    under the interpreter lock, so the plain add loses nothing."""

    def inc(self, n: int = 1) -> None:
        self._value += n
        self._uncounted += n


class GcClock:
    """Microseconds spent inside garbage collections, of every
    generation, in the counter ``runtime/gc/pause_us``; those of the
    full (generation 2) collections alone in
    ``runtime/gc/full_pause_us``, their number in
    ``runtime/gc/full_collections``. With the tracer on, a full
    collection also becomes a ``runtime/gc`` span under the span it
    interrupted. A collection stops every thread of the process, so a
    server's count over a window, per request, is what the collector
    cost each request.

    What a full collection costs is the heap it walks, and in a process
    that has compiled a kernel nearly all of that heap is what tracing
    and lowering left in JAX's caches: hundreds of thousands of objects
    that never die. `settle` moves them out of the collector's sight
    once a compile is over (``runtime/gc/settles``,
    ``runtime/gc/frozen_objects``)."""

    COUNTER = "runtime/gc/pause_us"
    FULL_COUNTER = "runtime/gc/full_pause_us"
    FULL_COLLECTIONS = "runtime/gc/full_collections"
    SETTLES = "runtime/gc/settles"
    FROZEN = "runtime/gc/frozen_objects"

    def __init__(self) -> None:
        self._installed = False
        self._pause_us: Optional[metrics.Counter] = None
        self._full_pause_us: Optional[metrics.Counter] = None
        self._full_collections: Optional[metrics.Counter] = None
        self._settles: Optional[metrics.Counter] = None
        self._frozen: Optional[metrics.Gauge] = None
        self._started = 0.0
        # full collections seen while the tracer was on, waiting to be
        # recorded from a point that holds none of the tracer's locks
        # (the callback may run inside them):
        # (start, end, tid, context, collected)
        self._spans: deque = deque(maxlen=256)

    def install(self, registry: metrics.Registry = metrics.DEFAULT_REGISTRY,
                settle_after=None) -> None:
        """Idempotent; the composition roots call it where they boot the
        registry's other always-on series. `settle_after` is the
        process's `devscope.CompileWatch`: the heap is settled after
        every compile of its that succeeded (`settle` becomes the
        watch's `after_compile`). Only a process that serves passes it. A
        library import or a test must not: a frozen cycle is never
        freed, and a test process holds thousands of objects that its
        tests expect `gc.collect()` to free."""
        if not self._installed:
            def pause_counter(name):
                return registry._get_or_register(name, _PauseCounter)

            self._pause_us = pause_counter(self.COUNTER)
            self._full_pause_us = pause_counter(self.FULL_COUNTER)
            self._full_collections = pause_counter(self.FULL_COLLECTIONS)
            self._settles = registry.counter(self.SETTLES)
            self._frozen = registry.gauge(self.FROZEN)
            gc.callbacks.append(self._on_gc)
            self._installed = True
        if settle_after is not None:
            settle_after.after_compile = self.settle

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._started = time.monotonic()
            return
        end = time.monotonic()
        pause_us = int((end - self._started) * 1e6)
        self._pause_us.inc(pause_us)
        if info.get("generation") != 2:
            return
        self._full_pause_us.inc(pause_us)
        self._full_collections.inc()
        if TRACER.enabled:
            stack = _SPAN_STACK.get()
            ctx = (stack[-1].trace_id, stack[-1].span_id) if stack else None
            self._spans.append((self._started, end, threading.get_ident(),
                                ctx, info.get("collected", 0)))

    def settle(self, op: str, shape: tuple) -> None:
        """Collect what is dead, then move everything that survived into
        the collector's permanent generation. Later full collections
        walk only what was allocated since; the collector stays enabled
        and its thresholds stay as they were, so a cycle made after this
        is found as soon as before. A frozen object is still freed when
        its last reference goes; only a cycle that was alive when frozen
        and dies later is kept for good, which is why nothing but a
        serving process, after a compile, calls this. The compile
        watch's `after_compile`: called after the first dispatch of `op`
        at `shape` has launched, on the thread that launched it."""
        with TRACER.start("runtime/gc/settle",
                          {"op": op, "shape": list(shape)}) as span:
            gc.collect()
            gc.freeze()
            frozen = gc.get_freeze_count()
            span.tag(frozen_objects=frozen)
        self._frozen.set(frozen)
        self._settles.inc()
        self.flush_spans()  # the collection above, under the settle

    def flush_spans(self) -> None:
        """Record the full collections the callback has put aside. Every
        traced stage calls it as it ends; a reader may call it before it
        reads the ring."""
        while self._spans:
            try:
                start, end, tid, ctx, collected = self._spans.popleft()
            except IndexError:
                return  # another thread took the last one
            TRACER.record("runtime/gc", start, end,
                          trace_id=ctx[0] if ctx else None,
                          parent_id=ctx[1] if ctx else None, tid=tid,
                          tags={"generation": 2, "collected": collected})


# THE process's collector clock (the TRACER analog)
GC_CLOCK = GcClock()
