"""Compile observability: what XLA compilation actually costs, and when
it storms.

`jax.jit` compiles once per argument SHAPE; the sig backends already
count per-shape cache hits/misses (`jax/compile_cache/*`), but a count
is not a cost — a recompile storm (unbucketed traffic widening the
shape set, a knob change invalidating every cached program) shows up
as mystery latency with nothing attributing it. This module closes
that gap:

- **Per-(op, shape) compile ledger.** The sig backend brackets every
  FIRST dispatch of a new (op, shape) with ``compile_span``; the wall
  time of that launch (trace + XLA compile + enqueue, or a load from
  the executable store: ``source``) lands here as that shape's
  compile cost. ``devscope/compile/{count,total_s}`` run
  as registry rows; per-shape detail rides ``describe()`` → the
  /status ``devscope`` section. One listener (``after_compile``)
  hears of each compile that succeeded: a serving process settles its
  heap there.
- **Recompile-storm detector.** Fresh-shape sightings feed a sliding
  window (``GETHSHARDING_DEVSCOPE_STORM_WINDOW_S``); when the window
  holds ``GETHSHARDING_DEVSCOPE_STORM_SHAPES`` or more, the detector
  raises ONCE per episode: a ``recompile_storm`` flight-recorder
  event, a ``devscope/compile/storms`` counter tick, and the
  ``devscope/compile/storm`` gauge latched to 1 until the window
  drains — an alertable row, not a log line. Steady-state traffic
  (cache hits, the occasional genuinely new bucket) never fires.

The hot path is one method call per dispatch with an early return on
cache hits; the timed path runs only on compiles, which cost seconds —
the bracket is free where it matters.
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading
import time
from collections import deque
from typing import Dict, Optional

from gethsharding_tpu import metrics

# registered at import: prom rows exist from the first scrape. Each
# CompileWatch resolves its rows through ITS registry (a drill watch —
# bench's storm injection, test fixtures — must not latch the process
# storm gauge); for the default-registry process watch these are the
# same instances.
metrics.counter("devscope/compile/count")
metrics.counter("devscope/compile/storms")
metrics.gauge("devscope/compile/storm")
metrics.gauge("devscope/compile/total_s")

DEFAULT_STORM_SHAPES = 8
DEFAULT_STORM_WINDOW_S = 30.0
_SHAPE_DETAIL_MAX = 512  # per-(op, shape) entries kept for describe()


def _storm_shapes() -> int:
    return int(os.environ.get("GETHSHARDING_DEVSCOPE_STORM_SHAPES",
                              str(DEFAULT_STORM_SHAPES)))


def _storm_window_s() -> float:
    return float(os.environ.get("GETHSHARDING_DEVSCOPE_STORM_WINDOW_S",
                                str(DEFAULT_STORM_WINDOW_S)))


class CompileWatch:
    """Per-shape compile cost ledger + sliding-window storm detector."""

    def __init__(self, storm_shapes: Optional[int] = None,
                 storm_window_s: Optional[float] = None,
                 clock=time.monotonic,
                 registry: metrics.Registry = metrics.DEFAULT_REGISTRY):
        self._lock = threading.Lock()
        self._clock = clock  # injectable: the storm tests seed time
        self._storm_shapes = (_storm_shapes() if storm_shapes is None
                              else int(storm_shapes))
        self._storm_window_s = (_storm_window_s() if storm_window_s is None
                                else float(storm_window_s))
        self.registry = registry
        self._m_compiles = registry.counter("devscope/compile/count")
        self._m_storms = registry.counter("devscope/compile/storms")
        self._g_storm = registry.gauge("devscope/compile/storm")
        self._g_total_s = registry.gauge("devscope/compile/total_s")
        # (op, shape) -> {"compiles": n, "wall_s": total}
        self._shapes: Dict[tuple, dict] = {}
        self._fresh_ts: deque = deque()  # fresh-shape sighting times
        self._in_storm = False
        self.total_s = 0.0
        self.compiles = 0
        self.from_store = 0  # of them, loaded from the executable store
        self.storms = 0
        # called as after_compile(op, shape) once a compile_span's body
        # has succeeded, on the thread that compiled and outside the
        # lock; a composition root sets it (`GcClock.install`)
        self.after_compile = None

    # -- producer API ------------------------------------------------------

    def saw(self, op: str, shape: tuple, fresh: bool) -> None:
        """One dispatch passed the backend's per-shape cache. Hits are
        a no-op; fresh shapes advance the storm window."""
        if not fresh:
            return
        now = self._clock()
        storm_onset = False
        fresh_now = 0
        with self._lock:
            key = (op, tuple(shape))
            if key not in self._shapes and \
                    len(self._shapes) < _SHAPE_DETAIL_MAX:
                self._shapes[key] = {"compiles": 0, "wall_s": 0.0}
            self._fresh_ts.append(now)
            horizon = now - self._storm_window_s
            while self._fresh_ts and self._fresh_ts[0] < horizon:
                self._fresh_ts.popleft()
            if len(self._fresh_ts) >= self._storm_shapes:
                if not self._in_storm:
                    self._in_storm = True
                    self.storms += 1
                    storm_onset = True
                    fresh_now = len(self._fresh_ts)
                    # gauge flips UNDER the lock (Gauge.set is a plain
                    # attr write): onset and drain publish in the order
                    # the verdict actually changed — two racing saw()
                    # calls can't leave it latched wrong
                    self._g_storm.set(1)
            elif self._in_storm:
                self._in_storm = False
                self._g_storm.set(0)
        if storm_onset:
            self._m_storms.inc()
            rate = fresh_now / max(self._storm_window_s, 1e-9)
            # lazy: a storm is a flight-recorder moment, but the watch
            # itself must not pull the recorder in on import; emitted
            # OUTSIDE the lock (the recorder takes its own)
            from gethsharding_tpu.perfwatch.recorder import RECORDER

            RECORDER.record("recompile_storm", op=op,
                            fresh_shapes=fresh_now,
                            window_s=self._storm_window_s,
                            shapes_per_s=round(rate, 3))

    def note_compile(self, op: str, shape: tuple, wall_s: float,
                     source: str = "traced",
                     load_s: Optional[float] = None) -> None:
        """Book one compile's wall time against its (op, shape), and
        where its executable came from: ``traced`` (traced, lowered,
        compiled or read from JAX's compile cache) or ``store`` (loaded
        by shape from the executable store, in `load_s` of the wall)."""
        with self._lock:
            key = (op, tuple(shape))
            slot = self._shapes.get(key)
            if slot is None and len(self._shapes) < _SHAPE_DETAIL_MAX:
                slot = self._shapes[key] = {"compiles": 0, "wall_s": 0.0}
            if slot is not None:
                slot["compiles"] += 1
                slot["wall_s"] += wall_s
                slot["source"] = source
                if load_s is not None:
                    slot["load_s"] = slot.get("load_s", 0.0) + load_s
            self.compiles += 1
            if source == "store":
                self.from_store += 1
            self.total_s += wall_s
            total = self.total_s
        self._m_compiles.inc()
        self._g_total_s.set(round(total, 4))

    @contextlib.contextmanager
    def compile_span(self, op: str, shape: tuple, fresh: bool):
        """Bracket a kernel launch: on a fresh shape the body's wall
        time (trace + compile + enqueue) is booked as the compile cost;
        on a cache hit this is one branch and a yield. A fresh shape's
        body is handed the booking, ``{"source": "traced"}``: one that
        loaded its executable from the store writes ``source="store"``
        and ``load_s`` into it. A launch that raised is booked too, but
        `after_compile` hears only of one that succeeded, and what it
        raises is logged, not passed on: the dispatch has its verdict
        by then."""
        if not fresh:
            yield None
            return
        booking = {"source": "traced"}
        t0 = time.perf_counter()
        try:
            yield booking
        finally:
            self.note_compile(op, shape, time.perf_counter() - t0,
                              **booking)
        after_compile = self.after_compile
        if after_compile is None:
            return
        try:
            after_compile(op, tuple(shape))
        except Exception:  # noqa: BLE001 - never into the dispatch
            logging.getLogger("devscope.compile").exception(
                "after_compile failed for %s %s", op, shape)

    # -- consumers ---------------------------------------------------------

    def storm_active(self) -> bool:
        """Live verdict: is the fresh-shape window still over the
        threshold? Also drains the window (and the latched gauge) when
        the storm has passed — read by /status, the detector tests,
        and the booted memory poller's periodic tick (so a
        Prometheus-only scraper sees the gauge clear without anyone
        hitting /status)."""
        now = self._clock()
        with self._lock:
            horizon = now - self._storm_window_s
            while self._fresh_ts and self._fresh_ts[0] < horizon:
                self._fresh_ts.popleft()
            if len(self._fresh_ts) < self._storm_shapes:
                self._in_storm = False
            active = self._in_storm
            if not active:
                self._g_storm.set(0)  # under the lock, like saw()
        return active

    def describe(self, top: int = 12) -> dict:
        active = self.storm_active()
        with self._lock:
            shapes = sorted(
                self._shapes.items(), key=lambda kv: -kv[1]["wall_s"])
            out = {
                "compiles": self.compiles,
                "from_store": self.from_store,
                "total_s": round(self.total_s, 4),
                "unique_shapes": len(self._shapes),
                "storms": self.storms,
                "storm_active": active,
                "window_fresh": len(self._fresh_ts),
                "storm_threshold": self._storm_shapes,
                "storm_window_s": self._storm_window_s,
                "top_shapes": [
                    {"op": key[0], "shape": list(key[1]),
                     "compiles": slot["compiles"],
                     "wall_s": round(slot["wall_s"], 4),
                     "source": slot.get("source"),
                     **({"load_s": round(slot["load_s"], 4)}
                        if "load_s" in slot else {})}
                    for key, slot in shapes[:top]],
            }
        return out


# THE process compile watch (the tracing.TRACER analog): the sig
# backend's per-shape cache feeds here; /status and the ledger read.
COMPILES = CompileWatch()
