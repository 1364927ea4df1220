"""Trustworthy device timing: force the pull, distrust the block.

JAX dispatch is asynchronous, and a timing that closes before the
device finished measures the enqueue. `DeviceTimer` is the one timing
primitive every dispatch site in `sigbackend/` and `serving/` uses:

- **The pull is the clock.** `pull(x)` materializes the value on the
  host (`np.asarray`) — the only operation that provably waits for
  the device — and the device phase closes only after it.
- **The block is the self-check.** Before pulling, the timer times
  ``block_until_ready()`` when the value has one. A block that
  returned near-instantly while the subsequent pull paid the real
  dispatch latency means the block did not wait: the timer
  increments the always-on ``perfwatch/timer_suspect`` counter,
  stamps itself ``suspect``, and drops a flight-recorder event — and
  the ledger writer marks any measurement taken over a suspect window
  ``valid: false`` so the regression gate never baselines a lie.
- **The rollups ride along.** `dispatched()`/`done()` feed the
  existing ``sig/marshal_time`` / ``sig/device_time`` registry timers
  (the fleet federation's "which replica's chip is slow" feed), so
  adopting the timer is not a second bookkeeping scheme.
- **The parts of the device phase are stages.** `pull()` takes the
  wait (``sig/block_time``) and the device-to-host copy
  (``sig/pull_time``) as `tracing.stage`s; a dispatch site takes its
  launch (``sig/launch_time``) the same way under `span_ctx`, and
  `record_span()` closes the dispatch's own span over them, so
  ``launch + block + pull <= sig/device_time`` in the registry and in
  a trace alike.

Thresholds: a pull under ``GETHSHARDING_PERFWATCH_SUSPECT_FLOOR_S``
(default 0.25 s) is never suspect; above it, the block must have
covered at least ``GETHSHARDING_PERFWATCH_SUSPECT_RATIO`` (default
0.1) of the pull time or the block is judged a no-op. The floor keeps
the verdict-plane transfer of an overlapped audit (device work done
before the pull, near-instant block, a short honest pull) out of the
net: the class the check exists for is a block hiding a whole
DISPATCH, which clears a 0.25 s floor with room; operators can lower
the floor to tighten the net.
"""

from __future__ import annotations

import os
import time
from typing import Optional

import numpy as np

from gethsharding_tpu import metrics, tracing

# registered at import: the /metrics?format=prom row exists from the
# first scrape, not the first suspect
_M_SUSPECT = metrics.counter("perfwatch/timer_suspect")
_M_PULLS = metrics.counter("perfwatch/pulls")
_T_MARSHAL = metrics.timer("sig/marshal_time")
_T_DEVICE = metrics.timer("sig/device_time")
_T_BLOCK = metrics.timer("sig/block_time")
_T_PULL = metrics.timer("sig/pull_time")


def _suspect_floor_s() -> float:
    return float(os.environ.get(
        "GETHSHARDING_PERFWATCH_SUSPECT_FLOOR_S", "0.25"))


def _suspect_ratio() -> float:
    return float(os.environ.get(
        "GETHSHARDING_PERFWATCH_SUSPECT_RATIO", "0.1"))


def suspect_count() -> int:
    """Process-lifetime ``perfwatch/timer_suspect`` total (the ledger
    writer and bench harness diff this around a measurement window)."""
    return _M_SUSPECT.value


def _checked_materialize(value, op: str):
    """block (timed) -> pull (timed) -> suspect verdict. Returns
    (host_array, block_s, pull_s, suspect)."""
    t0 = time.monotonic()
    block = getattr(value, "block_until_ready", None)
    if block is not None:
        block()
    t1 = time.monotonic()
    arr = np.asarray(value)
    t2 = time.monotonic()
    block_s, pull_s = t1 - t0, t2 - t1
    return arr, block_s, pull_s, _verdict(op, block is not None,
                                          block_s, pull_s)


def _verdict(op: str, blocked: bool, block_s: float, pull_s: float) -> bool:
    """Count one pull; True, counted and recorded, where the block did
    not wait for what the pull then paid."""
    _M_PULLS.inc()
    suspect = (blocked
               and pull_s > _suspect_floor_s()
               and block_s < pull_s * _suspect_ratio())
    if suspect:
        _M_SUSPECT.inc()
        # lazy import: recorder -> ledger -> (nothing heavy); kept lazy
        # anyway so a timer-only consumer never builds the recorder
        from gethsharding_tpu.perfwatch.recorder import RECORDER

        RECORDER.record("timer_suspect", op=op,
                        block_s=round(block_s, 6),
                        pull_s=round(pull_s, 6))
    return suspect


def checked_pull(value, op: str = "pull") -> np.ndarray:
    """Materialize a device value on the host with the block-vs-pull
    self-check, WITHOUT the marshal/device stage rollups — the
    one-shot form (`ensure_host`, probe scripts)."""
    arr, _, _, _ = _checked_materialize(value, op)
    return arr


def ensure_host(value, op: str = "dispatch"):
    """The serving tier's guard: the dispatch-latency clock must close
    over completed work. A bare device value is checked-pulled; a
    list/tuple whose ELEMENTS are lazy device scalars (the realistic
    shape of a backend leaking async buffers through the batch
    contract) gets one checked pull on its first element as the
    barrier — all outputs of one dispatch complete together, so one
    pull forces the batch. Plain host containers pay one isinstance +
    one hasattr."""
    if isinstance(value, (list, tuple)):
        if value and hasattr(value[0], "block_until_ready"):
            checked_pull(value[0], op=op)
        return value
    if value is None:
        return value
    if hasattr(value, "block_until_ready") or isinstance(value, np.ndarray):
        return checked_pull(value, op=op)
    return value


class DeviceTimer:
    """Per-dispatch stage clock: marshal -> dispatch -> pull.

    Usage at a dispatch site::

        dt = DeviceTimer("bls_committee")   # marshal phase opens
        ... host marshalling / staging ...
        dt.dispatched()                     # marshal closes, device opens
        out = fn(*args)                     # async launch
        arr = dt.pull(out)                  # block-check + REAL pull
        dt.done()                           # device closes, rollups fed

    `marshal_s` / `device_s` / `block_s` / `pull_s` / `suspect` are
    readable afterwards; `record_span()` records the dispatch's span
    with `t_dispatch` / `t_done` as its bounds, so span and rollup
    agree. With the tracer on, that span's id is taken at construction
    (`span_ctx`), under whatever span the constructing thread has open:
    the stages of the device phase parent under it, wherever `pull()`
    later runs."""

    __slots__ = ("op", "t_start", "t_dispatch", "t_done", "marshal_s",
                 "device_s", "block_s", "pull_s", "suspect", "_observed",
                 "span_ctx", "_span_parent")

    def __init__(self, op: str):
        self.op = op
        # (trace_id, span_id) of this dispatch's span, None with the
        # tracer off
        self.span_ctx = None
        self._span_parent = None
        tracer = tracing.TRACER
        if tracer.enabled:
            outer = tracer.current()
            self.span_ctx = (outer[0] if outer else tracer.new_trace_id(),
                             tracer.new_trace_id())
            self._span_parent = outer[1] if outer else None
        self.t_start = time.monotonic()
        self.t_dispatch: Optional[float] = None
        self.t_done: Optional[float] = None
        self.marshal_s = 0.0
        self.device_s = 0.0
        self.block_s = 0.0
        self.pull_s = 0.0
        self.suspect = False
        self._observed = False

    def dispatched(self) -> "DeviceTimer":
        """Close the marshal phase (feeds ``sig/marshal_time``) and
        open the device phase."""
        self.t_dispatch = time.monotonic()
        self.marshal_s = self.t_dispatch - self.t_start
        _T_MARSHAL.observe(self.marshal_s)
        return self

    def pull(self, value) -> np.ndarray:
        """Materialize `value` on the host with the block-vs-pull
        self-check; extends the device phase to now. May be called more
        than once (multi-output dispatches); `done()` closes the
        phase."""
        if self.t_dispatch is None:
            self.dispatched()
        block = getattr(value, "block_until_ready", None)
        with tracing.stage("sig/block_time", _T_BLOCK,
                           ctx=self.span_ctx) as blocked:
            if block is not None:
                block()
        with tracing.stage("sig/pull_time", _T_PULL,
                           ctx=self.span_ctx) as pulled:
            arr = np.asarray(value)
        self.block_s += blocked.seconds
        self.pull_s += pulled.seconds
        self.suspect = _verdict(self.op, block is not None, blocked.seconds,
                                pulled.seconds) or self.suspect
        self.t_done = time.monotonic()
        return arr

    def done(self) -> "DeviceTimer":
        """Close the device phase (feeds ``sig/device_time``).
        Idempotent — later calls keep the first observation."""
        if self._observed:
            return self
        if self.t_dispatch is None:
            self.dispatched()
        self.t_done = time.monotonic()
        self.device_s = self.t_done - self.t_dispatch
        _T_DEVICE.observe(self.device_s)
        self._observed = True
        return self

    def record_span(self, name: str, **tags) -> None:
        """Record the dispatch's span, `t_dispatch` to `t_done`, in the
        trace of the span that was open when this timer was made, with
        the timer's own readings among its tags: `marshal_ms` is
        `marshal_s`, everything before `dispatched()`, so on the
        committee spans host marshal plus transfer staging. Nothing
        where the tracer was off then."""
        if self.span_ctx is None:
            return
        tracing.TRACER.record(
            name, self.t_dispatch, self.t_done,
            trace_id=self.span_ctx[0], parent_id=self._span_parent,
            span_id=self.span_ctx[1],
            tags={**tags, "suspect": self.suspect,
                  "marshal_ms": round(self.marshal_s * 1e3, 3),
                  "device_ms": round(self.device_s * 1e3, 3)})
